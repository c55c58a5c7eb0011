"""Reduce a profiler trace (``.xplane.pb``) to the numbers per-layer metrics
read: device busy time, time per executable and per operation, the Pallas
kernels' events, and the longest device gaps with what the host did in them.

Device planes are ``/device:TPU:<n>``. Their ``XLA Modules`` line holds one
event per executable run (named after the jitted function, e.g.
``jit_burst(...)``); their ``XLA Ops`` line one event per operation, named by
its HLO instruction text (``%fusion.12 = bf16[..] fusion(...)``; a Pallas
call is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``).
Control-flow ops (while, conditional, call) span their bodies and are left
out. Busy time is the union of the remaining operation intervals, averaged
over the chips traced. Host planes give the Python thread's spans (the
harness's ``TraceAnnotation``s among them).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil
from typing import Any, Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


# Control-flow ops span their bodies; only the ops inside them run.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class OpEvent:
    name: str                           # the HLO instruction, as traced
    module: str                         # the executable it ran in
    start_ns: float
    dur_ns: float

    @property
    def label(self) -> str:
        """``<opcode> <output shape>``: a name that survives renumbering."""
        return "%s %s" % op_kind(self.name)


def op_kind(text: str) -> Tuple[str, str]:
    """(opcode, output shape without layouts) of an HLO instruction text."""
    rhs = text.partition(" = ")[2] or text
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rhs[:i + 1], rhs[i + 1:].lstrip()
        shape = "(tuple)"
    else:
        shape, _, rest = rhs.partition(" ")
        shape = shape.split("{")[0]
    return rest.partition("(")[0], shape


@dataclasses.dataclass
class TraceSummary:
    n_devices: int
    busy_s: float                       # union of op intervals, per chip
    modules: Dict[str, List[Interval]]  # executable -> its runs
    ops: List[OpEvent]
    gaps: List[Tuple[float, str]]       # (seconds idle, host activity)

    def module_time(self, match: str) -> Tuple[int, float]:
        """(runs, device seconds) of executables whose name holds ``match``."""
        runs = [iv for name, ivs in self.modules.items() if match in name
                for iv in ivs]
        return len(runs), sum(e - s for s, e in runs) * 1e-9

    def op_totals(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for op in self.ops:
            key = f"{op.module.split('(')[0]}:{op.label}"
            out[key] = out.get(key, 0.0) + op.dur_ns * 1e-9
        return out

    def breakdown(self) -> Dict[str, List[List[Any]]]:
        top = sorted(self.op_totals().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[what, s] for s, what in self.gaps[:10]]}


def union_length(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def find_xplane(directory: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def summarize(directory: str) -> Optional[TraceSummary]:
    path = find_xplane(directory)
    return None if path is None else summarize_file(path)


def summarize_file(path: str) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")
               and "Core" not in p.name]
    if not devices:
        return None
    busy, modules, ops = [], {}, []
    for plane in devices:
        dev_ops: List[Interval] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    modules.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    if op_kind(ev.name)[0] in CONTAINERS:
                        continue
                    ops.append(OpEvent(ev.name, "", ev.start_ns,
                                       ev.duration_ns))
                    dev_ops.append((ev.start_ns, ev.start_ns
                                    + ev.duration_ns))
        busy.append(union_length(dev_ops))
    if not ops:
        return None
    _attach_modules(ops, modules)
    return TraceSummary(n_devices=len(devices),
                        busy_s=sum(busy) / len(busy) * 1e-9,
                        modules=modules, ops=ops,
                        gaps=_gaps(devices[0], pd))


def _attach_modules(ops: List[OpEvent], modules) -> None:
    """Name each op's executable from the module run that encloses it."""
    runs = sorted((s, e, name) for name, ivs in modules.items()
                  for s, e in ivs)
    starts = [r[0] for r in runs]
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and runs[i][1] >= op.start_ns:
            op.module = runs[i][2]


def _gaps(device_plane, pd, top: int = 10) -> List[Tuple[float, str]]:
    """The longest idle stretches between device ops, each named by the
    innermost host span (Python thread) that covers its midpoint."""
    ivs = []
    for line in device_plane.lines:
        if line.name == "XLA Ops":
            ivs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events
                         if op_kind(ev.name)[0] not in CONTAINERS)
    gaps, end = [], None
    for s, e in ivs:
        if end is not None and s > end:
            gaps.append((s - end, end, s))
        end = e if end is None else max(end, e)
    gaps.sort(reverse=True)
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name == "python" or line.name.startswith("python"):
                    host.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in line.events)
    out = []
    for length, s, e in gaps[:top]:
        mid = (s + e) / 2
        cover = [h for h in host if h[0] <= mid <= h[1]]
        what = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "none"
        out.append((length * 1e-9, what))
    return out


def remove(directory: str) -> None:
    shutil.rmtree(directory, ignore_errors=True)
