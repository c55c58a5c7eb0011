"""Device time per layer of the train step, read from each op's own scope.

The program opens a ``jax.named_scope`` at each layer boundary of the step
(``LAYERS``) and names every Pallas call (``KERNELS``); both become part of
the op name that XLA keeps in each HLO instruction's metadata, and the
profiler records that name as the ``tf_op`` stat of the op's metadata on a
device plane's ``XLA Ops`` line, e.g.
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/moe/
cvmm_dw_streamed/pallas_call``. ``jax.profiler.ProfileData`` gives event
stats but not metadata stats, so this module reads the ``.xplane.pb`` (an
``XSpace`` protobuf, field numbers as in tsl's ``xplane.proto``) with a
short wire-format reader of its own, and only the device planes of it.

An op's layer is the outermost component of its ``tf_op`` path that names a
layer, once the transform wrappers (``jvp(...)``, ``transpose(...)``) are
taken off: a layer's forward, its recomputed forward (``checkpoint/
rematted_computation/<layer>``) and its backward all land on it. Only leaf
ops count (``trace.CONTAINERS`` span their bodies), and only those inside
runs of the executable asked for (its ``XLA Modules`` events). Ops that no
layer scope names are ``uncovered``: XLA's own copies, loop bookkeeping,
and every op of a program that opens no scopes.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import time
from typing import Dict, List, Optional, Tuple

from chipbench import trace

LAYERS = ("embed", "attention", "moe", "loss", "optimizer", "block")
KERNELS = ("cvmm_fwd", "cvmm_dw", "cvmm_fused_w1", "cvmm_gather_rows",
           "cvmm_dw_streamed", "cvmm_fused_w2", "flash_attention")
# Named parts of a layer, reported inside it: the kernels and the relative
# shift of xl_rel attention.
PARTS = KERNELS + ("rel_shift",)

_WRAPPED = re.compile(r"^(?:(?:jvp|transpose|vmap)\()+(.*?)\)*$")


def components(tf_op: str) -> List[str]:
    """The scope names of a ``tf_op`` path, transform wrappers taken off
    (the text after the last ':' is the op's type, not a scope)."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    out = []
    for part in path.split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return out


# ---------------------------------------------------------------------------
# XSpace wire format
# ---------------------------------------------------------------------------

def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result, shift = 0, 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes, start: int, end: int):
    """(field number, value) of the message in ``buf[start:end]``; the value
    of a length-delimited field is the (start, end) of its bytes, fixed-width
    fields are skipped."""
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield key >> 3, value
        elif wire == 2:
            n, pos = _varint(buf, pos)
            yield key >> 3, (pos, pos + n)
            pos += n
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _message(buf: bytes, span: Tuple[int, int]) -> Dict[int, list]:
    """A message's fields: field number -> its values, in order."""
    out: Dict[int, list] = {}
    for num, value in _fields(buf, *span):
        out.setdefault(num, []).append(value)
    return out


def _one(msg: Dict[int, list], num: int, default=0):
    return msg[num][-1] if num in msg else default


def _str(buf: bytes, msg: Dict[int, list], num: int) -> str:
    return _text(buf, msg[num][-1]) if num in msg else ""


# XSpace: planes 1. XPlane: name 2, lines 3, event_metadata 4 and
# stat_metadata 5 (map entries: key 1, value 2). XLine: name 2,
# timestamp_ns 3, events 4. XEvent: metadata_id 1, offset_ps 2,
# duration_ps 3. XEventMetadata: name 2, stats 5. XStatMetadata: id 1,
# name 2. XStat: metadata_id 1, str_value 5, ref_value 7.

def _device_planes(buf: bytes):
    """Each TPU chip's plane as (lines, metadata): the ``XLA Ops`` and
    ``XLA Modules`` lines as (metadata id, start ps, duration ps) events, and
    the metadata those events name as id -> (name, tf_op)."""
    for span in _message(buf, (0, len(buf))).get(1, []):
        plane = _message(buf, span)
        name = _str(buf, plane, 2)
        if name.startswith("/device:TPU:") and "Core" not in name:
            yield _read_plane(buf, plane)


def _read_plane(buf: bytes, plane: Dict[int, list]):
    lines = {}
    for span in plane.get(3, []):
        line = _message(buf, span)
        name = _str(buf, line, 2)
        if name in ("XLA Ops", "XLA Modules"):
            t0 = _one(line, 3) * 1000
            events = (_message(buf, ev) for ev in line.get(4, []))
            lines[name] = [(_one(e, 1), t0 + _one(e, 2), _one(e, 3))
                           for e in events]
    stat_names = {}
    for entry in plane.get(5, []):
        meta = _message(buf, _one(_message(buf, entry), 2, (0, 0)))
        stat_names[_one(meta, 1)] = _str(buf, meta, 2)
    tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    wanted = {ev[0] for events in lines.values() for ev in events}
    metadata = {}
    for entry in plane.get(4, []):
        entry = _message(buf, entry)
        key = _one(entry, 1)
        if key not in wanted:
            continue
        meta = _message(buf, _one(entry, 2, (0, 0)))
        tf_op = ""
        for span in meta.get(5, []):
            stat = _message(buf, span)
            if tf_op_id is not None and _one(stat, 1) == tf_op_id:
                tf_op = (_str(buf, stat, 5) if 5 in stat
                         else stat_names.get(_one(stat, 7), ""))
        metadata[key] = (_str(buf, meta, 2), tf_op)
    return lines, metadata


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScopeSummary:
    """Device seconds of one executable's leaf ops, summed over its traced
    runs (``steps``) and the chips traced, by layer and by named part."""
    steps: int
    module_s: float                 # the executable's runs, end to end
    layers: Dict[str, float]
    uncovered_s: float
    parts: Dict[str, float]         # kernel name or rel_shift -> seconds
    unnamed_kernels_s: float        # Pallas calls that carry no KERNELS name
    parse_s: float

    @property
    def ops_s(self) -> float:
        return sum(self.layers.values()) + self.uncovered_s

    def ms_per_step(self, seconds: float) -> float:
        return 1e3 * seconds / self.steps

    def layer_ms(self, layer: str) -> Optional[float]:
        """Device ms per traced step under ``layer``; None when no op ran
        under it."""
        s = self.layers.get(layer, 0.0)
        return self.ms_per_step(s) if s > 0 and self.steps else None

    def uncovered_share(self) -> float:
        return self.uncovered_s / self.ops_s if self.ops_s > 0 else 0.0

    def line(self) -> str:
        ms = self.ms_per_step
        layers = ", ".join(f"{k} {ms(v):.3f}" for k, v in sorted(
            self.layers.items(), key=lambda kv: -kv[1]))
        parts = ", ".join(f"{k} {ms(v):.3f}" for k, v in sorted(
            self.parts.items(), key=lambda kv: -kv[1]))
        return (f"scopes over {self.steps} steps, ms/step: {layers or 'none'}"
                f"; uncovered {ms(self.uncovered_s):.3f} "
                f"({100 * self.uncovered_share():.2f}% of op time); module "
                f"{ms(self.module_s):.3f}; parts {parts or 'none'}; unnamed "
                f"Pallas calls {ms(self.unnamed_kernels_s):.3f}; parsed in "
                f"{self.parse_s:.2f}s")


def summarize_file(path: str, module_match: str = "train_step"
                   ) -> Optional[ScopeSummary]:
    """The reduction of one ``.xplane.pb``; None when it holds no run of a
    matching executable."""
    t0 = time.perf_counter()
    with open(path, "rb") as f:
        buf = f.read()
    steps, module_ps, uncovered, unnamed = 0, 0, 0, 0
    layers: Dict[str, float] = {}
    parts: Dict[str, float] = {}
    for lines, metadata in _device_planes(buf):
        runs = sorted((s, s + d) for m, s, d in lines.get("XLA Modules", ())
                      if module_match in metadata[m][0])
        steps += len(runs)
        module_ps += sum(e - s for s, e in runs)
        starts = [s for s, _ in runs]
        for meta, start, dur in lines.get("XLA Ops", ()):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start > runs[i][1]:
                continue
            text, tf_op = metadata[meta]
            if trace.op_kind(text)[0] in trace.CONTAINERS:
                continue
            names = components(tf_op)
            layer = next((n for n in names if n in LAYERS), None)
            if layer is None:
                uncovered += dur
            else:
                layers[layer] = layers.get(layer, 0) + dur
            part = next((n for n in names if n in PARTS), None)
            if part is not None:
                parts[part] = parts.get(part, 0) + dur
            if (part not in KERNELS
                    and 'custom_call_target="tpu_custom_call"' in text):
                unnamed += dur
    if not steps:
        return None
    return ScopeSummary(
        steps=steps, module_s=module_ps * 1e-12,
        layers={k: v * 1e-12 for k, v in layers.items()},
        uncovered_s=uncovered * 1e-12,
        parts={k: v * 1e-12 for k, v in parts.items()},
        unnamed_kernels_s=unnamed * 1e-12,
        parse_s=time.perf_counter() - t0)


def for_run(run, outcome) -> Optional[ScopeSummary]:
    """The run's reduction, parsed once and kept in ``outcome.facts`` for
    the other readers; logged when parsed. None without a trace."""
    facts = outcome.facts
    if "scopes" not in facts:
        path = trace.find_xplane(run.tracer.directory)
        summary = None if path is None else summarize_file(path)
        if summary is not None:
            from chipbench.harness import log
            log(summary.line())
        facts["scopes"] = summary
    return facts["scopes"]


def layer_ms(run, outcome, layer: str) -> Optional[float]:
    """A per-layer metric: device ms per traced step under ``layer``."""
    summary = for_run(run, outcome)
    return None if summary is None else summary.layer_ms(layer)
