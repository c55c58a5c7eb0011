"""Device time per traced train step under named scopes that lie inside a
layer: ``mla_latent`` (``models/attention.mla_qkv``: MLA's latent
down-projection, its norm, the up-projection and the decoupled RoPE) inside
``attention``, and ``router`` (``core/moe.apply_moe``: router logits,
scores, selection bias, top-k and the held-expert filter) inside ``moe``.

Read as ``chipbench/scopes.py`` reads layers, with its wire-format reader:
an op counts for a scope when the scope is a component of its ``tf_op``, in
the forward, the recomputed forward and the backward alike; only leaf ops
count, and only inside runs of the train step. A program that opens no such
scope gives None.
"""
from __future__ import annotations

import bisect
from typing import Dict, Optional

from chipbench import scopes, trace

NAMES = ("mla_latent", "router")


def summarize_file(path: str, module_match: str = "train_step"
                   ) -> Optional[Dict[str, float]]:
    """{"steps": traced runs of the step, <name>: device seconds}; None when
    the file holds no run of a matching executable."""
    with open(path, "rb") as f:
        buf = f.read()
    steps = 0
    spent = {name: 0 for name in NAMES}
    for lines, metadata in scopes._device_planes(buf):
        runs = sorted((s, s + d) for m, s, d in lines.get("XLA Modules", ())
                      if module_match in metadata[m][0])
        steps += len(runs)
        starts = [s for s, _ in runs]
        for meta, start, dur in lines.get("XLA Ops", ()):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start > runs[i][1]:
                continue
            text, tf_op = metadata[meta]
            if trace.op_kind(text)[0] in trace.CONTAINERS:
                continue
            names = scopes.components(tf_op)
            for name in NAMES:
                if name in names:
                    spent[name] += dur
    if not steps:
        return None
    return dict({k: v * 1e-12 for k, v in spent.items()}, steps=steps)


def scope_ms(run, outcome, name: str) -> Optional[float]:
    """Device ms per traced step under ``name``; None without a trace or
    when no op ran under it. The file is parsed once per run."""
    facts = outcome.facts
    if "named_scopes" not in facts:
        path = trace.find_xplane(run.tracer.directory)
        facts["named_scopes"] = None if path is None else summarize_file(path)
    s = facts["named_scopes"]
    if s is None or s[name] <= 0:
        return None
    return 1e3 * s[name] / s["steps"]
