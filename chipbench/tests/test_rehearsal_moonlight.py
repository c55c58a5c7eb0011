"""The Moonlight cell driven end to end at reduced size on the CPU, as
test_rehearsal.py drives the wt103 cell: the arch's reduced config with the
file's cut (6 layers, 8 of 16 experts held), interpret-mode kernels, set-up,
window, trace reading and the reference check, and the result line's
keys."""
import json

import pytest

from chipbench import harness
from chipbench.tests.rehearse import overrides
from chipbench.tests.test_rehearsal import SEED


@pytest.mark.parametrize("trace", [False, True])
def test_moonlight_cell_end_to_end(trace):
    workload = "train-moonlight-s8k"
    # read at this size on the CPU (3 seeds, ragged): grad_gap 0.022-0.032,
    # the float8 control 0.049-0.056 (the file's 0.006 is the chip's)
    run = harness.open_run(workload, SEED, 1.5, trace,
                           test=overrides(limits={"grad_gap": 0.04}))
    res = harness.execute(run)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    names = set(harness.metric_names(run.bench, workload, kind))
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
    json.dumps(res)
