"""Reduced-size overrides for driving the train cell end to end on the CPU:
the arch's reduced config, Pallas kernels in interpret mode (or ``ragged``),
a batch a CPU can step through in a second or two, and limits read at that
size."""
from __future__ import annotations

import copy

TRAIN = {"reduced": True, "impl": "pallas_fused_interpret",
         "traffic": {"batch": 2, "seq": 32, "pool": 4,
                     "trace_after_steps": 1, "trace_steps": 1},
         # read at this size on the CPU over 3 seeds (ragged): sound update
         # 0.014-0.022, layer1 0.0061-0.0063; the float8 control update
         # 0.082-0.113, layer1 0.088-0.094; half the batch update 0.25-0.27,
         # layer1 0.40-0.58; a state left unchanged update 1
         "limits": {"update_gap": 0.05, "layer1_gap": 0.03}}


def overrides(**extra):
    base = copy.deepcopy(TRAIN)
    for key, value in extra.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            base[key].update(value)
        else:
            base[key] = value
    return base
