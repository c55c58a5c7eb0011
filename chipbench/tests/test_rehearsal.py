"""The train cell driven end to end at reduced size on the CPU
(interpret-mode kernels): set-up, window, trace reading and the reference
check run as on the chip, and the result line has the contract's keys."""
import json

import pytest

from chipbench import harness
from chipbench.tests.rehearse import overrides

SEED = 2 ** 33 + 5          # beyond 32 bits, as the driver's seeds are


@pytest.mark.parametrize("workload,trace", [
    ("train-wt103-262m", False),
    ("train-wt103-262m", True),
])
def test_cell_end_to_end(workload, trace):
    run = harness.open_run(workload, SEED, 1.5, trace, test=overrides())
    res = harness.execute(run)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    names = set(harness.metric_names(run.bench, workload, kind))
    assert set(res["metrics"]) <= names
    if trace:
        assert res["device"]["window_s"] > 0
    else:
        assert set(res["metrics"]) == names
    json.dumps(res)
