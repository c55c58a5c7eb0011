"""What the per-layer metric files share: idle share, kernel roofline
shares and model FLOP utilization, read from a run's trace summary and the
driver's facts. Every reader returns None when its run has nothing for it to
read (no trace, no kernel)."""
from __future__ import annotations

import re
from typing import Callable, Optional, Tuple

from chipbench import counts


def _peak(run):
    """The chip's peaks; None off the chip, where no utilization exists."""
    import jax
    dev = jax.devices()[0]
    return counts.peaks(dev.device_kind) if dev.platform == "tpu" else None


def summary(outcome):
    return outcome.facts.get("trace")


def idle_share(run, outcome) -> Optional[float]:
    """100 * (1 - device busy seconds / traced window seconds)."""
    s, window = summary(outcome), outcome.facts.get("traced_window_s", 0.0)
    if s is None or window <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / window)


SHAPE = re.compile(r"\b(bf16|f32|f16|s32|u32|s8|pred)\[([\d,]*)\]")


def _shapes(text: str):
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in SHAPE.findall(text)]


def kernel_shapes(text: str):
    """(outputs, operands) of a Pallas call's trace event, or None when the
    event is not one. The event's name is the HLO instruction:
    ``%name = <outputs> custom-call(<operands>), custom_call_target=...``."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    head, _, rest = text.partition(" custom-call(")
    args = rest.partition("), custom_call_target")[0]
    return _shapes(head.partition(" = ")[2]), _shapes(args)


def kernel_cost(text: str, rows: int,
                experts: int) -> Optional[Tuple[float, float]]:
    """FLOPs and bytes that one Pallas call of the MoE needs for ``rows``
    routed (token, expert) rows, told apart by its shapes alone:
    a float32 (experts, a, b) output is a weight gradient; a call with an
    (experts, k_in, n) weight operand is a grouped GEMM writing one or more
    (rows, n) results; any other is a row gather."""
    shapes = kernel_shapes(text)
    if shapes is None:
        return None
    outs, ins = shapes
    if len(outs) == 1 and outs[0][0] == "f32" and len(outs[0][1]) == 3 \
            and outs[0][1][0] == experts:
        _, a, b = outs[0][1]
        return counts.grouped_dw(rows, a, b, experts)
    weights = [d for dt, d in ins if len(d) == 3 and d[0] == experts
               and dt == "bf16"]
    if weights:
        _, k_in, n = weights[-1]
        n_outs = sum(1 for _, d in outs if len(d) == 2 and d[-1] == n)
        return counts.grouped_gemm(rows, k_in, n, experts,
                                   outputs=max(n_outs, 1))
    if outs and len(outs[0][1]) == 2:
        return counts.row_gather(rows, outs[0][1][1])
    return None


def roofline(run, outcome, module_match: str,
             cost: Callable[[str], Optional[Tuple[float, float]]]
             ) -> Optional[float]:
    """Sum over the Pallas calls in matching executables of the least time
    the chip could take for each, over their summed device time, in %."""
    s, peak = summary(outcome), _peak(run)
    if s is None or peak is None:
        return None
    ideal = spent = 0.0
    for op in s.ops:
        if module_match not in op.module:
            continue
        c = cost(op.name)
        if c is None:
            continue
        ideal += counts.ideal_s(c[0], c[1], peak)
        spent += op.dur_ns * 1e-9
    return None if spent <= 0 else 100.0 * ideal / spent


def mfu(run, flops: float, seconds: float) -> Optional[float]:
    peak = _peak(run)
    if seconds <= 0 or flops <= 0 or peak is None:
        return None
    return 100.0 * flops / seconds / peak["bf16_flops_per_s"]

