"""Roofline share of the sigma-MoE kernels in the train step (%): over the
traced Pallas calls, the least time each could take (the FLOPs or bytes of
the step's N*k routed rows at peak, whichever binds) over their device
time. Forward, recomputed forward and backward calls all count."""
from chipbench import readers


def read(run, outcome):
    f = outcome.facts
    m, t = f["model"], f["traffic"]
    rows = t["batch"] * (t["seq"] + 1) * m["k"]
    return readers.roofline(
        run, outcome, "train_step",
        lambda text: readers.kernel_cost(text, rows, m["n_experts"]))
