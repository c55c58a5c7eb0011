"""The check catches a broken timed path: with a fault planted under the
step, or the float8 control put in the program's place, a training run at
reduced size on the CPU reports ``correct`` false, where the same run
without either reports true (limits read at this size). One chip has no
exchange between chips to leave out."""
import pytest

from chipbench import harness
from chipbench.tests.rehearse import overrides

CASES = [
    ("train-wt103-262m", None),
    ("train-wt103-262m", "state_unchanged"),
    ("train-wt103-262m", "half_batch"),
    ("train-wt103-262m", "control_fp8"),
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_caught(workload, fault):
    broken = ({"control": "fp8"} if fault == "control_fp8" else
              {"fault": fault} if fault else {})
    run = harness.open_run(workload, 2 ** 32 + 17, 1.0, False,
                           test=overrides(impl="ragged", **broken))
    res = harness.execute(run)
    assert res["correct"] is (fault is None), res["checks"]
