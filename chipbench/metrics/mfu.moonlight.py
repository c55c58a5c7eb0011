"""Model FLOP utilization of the train step (%): forward and backward FLOPs
per token (6 N_active with the held share of the routed experts, plus causal
MLA attention at head_dim for scores and v_head_dim for values;
chipbench/counts_mla.py) of the traced steps over the traced window's
host-clock seconds, over the chip's bf16 peak."""
from chipbench import counts_mla, readers


def read(run, outcome):
    f = outcome.facts
    flops = counts_mla.train_flops_per_token(f["model"], f["traffic"]["seq"])
    return readers.mfu(run, flops * f["traced_steps"] * f["tokens_per_step"],
                       f.get("traced_window_s", 0.0))
