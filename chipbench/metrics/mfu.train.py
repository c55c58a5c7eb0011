"""Model FLOP utilization of the train step (%): forward and backward
FLOPs per token (6 N_active plus xl_rel attention, recomputation not
counted) of the traced steps over the traced window's host-clock seconds,
over the chip's bf16 peak. The traced window spans those steps whole, with
their readbacks, and not the profiler's start and stop."""
from chipbench import counts, readers


def read(run, outcome):
    f = outcome.facts
    flops = counts.train_flops_per_token(f["model"], f["traffic"]["seq"])
    return readers.mfu(run, flops * f["traced_steps"] * f["tokens_per_step"],
                       f.get("traced_window_s", 0.0))
