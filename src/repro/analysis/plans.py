"""Pass 2: plan-invariant verifier for CvmmPlan / GatherPlan / DedupGatherPlan.

Every streamed kernel trusts its plan blindly: ``row_src`` routes HBM rows,
the ``run_start``/``run_off`` chunk table decides what each DMA descriptor
copies, ``sel_pos`` redirects per-token weighting. A wrong plan does not
crash — it silently gathers the wrong rows. This pass is the single oracle
for plan soundness; ``ops.plan_dma_stats(..., verify=True)`` and the property
suites call the same functions, so telemetry, tests and CI prove the same
contract.

``replay_chunk_table`` re-executes the chunk table in numpy EXACTLY the way
``cvmm._run_dmas`` walks it (one loop per static size class over the
``run_off`` boundaries), proving:

  class grouping     every entry inside class ci's boundary range describes a
                     chunk of exactly ``_RUN_SIZES[ci]`` rows; entries past
                     the last boundary are unused (``run_len == 0``)
  boundary legality  per-tile ``run_off`` starts at 0, is non-decreasing, and
                     never exceeds the tile's entry count
  chunk legality     chunks stay inside their tile and inside the source
                     array, and the source rows they claim are genuinely
                     contiguous in ``row_src`` (a DMA copies ``src..src+len``;
                     if ``row_src`` disagrees the copy lands wrong rows)
  exact coverage     every REAL slot (``row_src < n_rows``) is written by
                     exactly one chunk; sentinel slack slots by none

``verify_plan`` adds the per-plan-type structural invariants (permutations,
tile-expert consistency, the live bound, sorted-unique prefix, ``sel_pos``
indirection). ``check_plans`` sweeps the three builders plus
``dispatch.ep_local_plan`` over adversarial routings (skewed, colliding,
empty-expert, sub-tile, a held share of the experts).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..kernels import cvmm, ops
from .report import Finding

TM = ops.TM


def _bad(check: str, location: str, detail: str) -> Finding:
    return Finding("plans", check, location, detail)


def replay_chunk_table(plan, n_rows: int, x: Optional[np.ndarray] = None,
                       location: str = "plan"):
    """Numpy re-execution of the plan's DMA chunk table.

    Returns ``(out, n_dma, findings)``: the gathered tile-aligned array (zeros
    where no chunk writes; ``None`` when ``x`` is not given), the number of
    DMA descriptors a kernel pass would issue, and the invariant findings."""
    findings: List[Finding] = []
    rs = np.asarray(plan.row_src)
    rst = np.asarray(plan.run_start)
    rl = np.asarray(plan.run_len)
    nc = len(cvmm._RUN_SIZES)
    m_pad = rs.shape[0]
    n_tiles = m_pad // TM
    ro = np.asarray(plan.run_off)
    if ro.shape != (n_tiles * (nc + 1),):
        findings.append(_bad("run-off-shape", location,
                             f"run_off has shape {ro.shape}, expected "
                             f"({n_tiles * (nc + 1)},)"))
        return None, 0, findings
    ro = ro.reshape(n_tiles, nc + 1)
    out = None if x is None else np.zeros((m_pad, x.shape[1]), x.dtype)
    covered = np.zeros((m_pad,), np.int32)
    n_dma = 0
    for t in range(n_tiles):
        base = t * TM
        if ro[t, 0] != 0:
            findings.append(_bad("run-off-start", f"{location} tile {t}",
                                 f"first class boundary is {ro[t, 0]}, not 0"))
        if np.any(np.diff(ro[t]) < 0) or ro[t, nc] > TM:
            findings.append(_bad(
                "run-off-bounds", f"{location} tile {t}",
                f"class boundaries {ro[t].tolist()} are not a non-decreasing "
                f"sequence within [0, {TM}]"))
            continue
        for ci, sz in enumerate(cvmm._RUN_SIZES):
            for j in range(ro[t, ci], ro[t, ci + 1]):
                n_dma += 1
                if rl[base + j] != sz:
                    findings.append(_bad(
                        "class-mismatch", f"{location} tile {t} entry {j}",
                        f"entry sits in size-class {sz} but run_len says "
                        f"{int(rl[base + j])} — the kernel would copy {sz}"))
                off = int(rst[base + j])
                if not (0 <= off and off + sz <= TM):
                    findings.append(_bad(
                        "chunk-tile-overrun", f"{location} tile {t} entry {j}",
                        f"chunk [{off}, {off + sz}) leaves the {TM}-slot tile"))
                    continue
                src = int(rs[base + off])
                if not (0 <= src and src + sz <= n_rows):
                    findings.append(_bad(
                        "chunk-src-overrun", f"{location} tile {t} entry {j}",
                        f"chunk reads source rows [{src}, {src + sz}) from an "
                        f"array of {n_rows} rows"))
                    continue
                run = rs[base + off: base + off + sz]
                if not np.array_equal(run, np.arange(src, src + sz)):
                    findings.append(_bad(
                        "chunk-noncontiguous", f"{location} tile {t} entry {j}",
                        f"chunk claims contiguous sources [{src}, {src + sz}) "
                        f"but row_src there is {run.tolist()} — the DMA would "
                        f"land the wrong rows"))
                covered[base + off: base + off + sz] += 1
                if out is not None:
                    out[base + off: base + off + sz] = x[src: src + sz]
        tail = rl[base + ro[t, nc]: base + TM]
        if np.any(tail != 0):
            findings.append(_bad(
                "tail-not-empty", f"{location} tile {t}",
                f"entries past the last class boundary must be unused "
                f"(run_len 0), found {tail[tail != 0].tolist()}"))
    valid = rs < n_rows
    over = np.nonzero(valid & (covered != 1))[0]
    if over.size:
        findings.append(_bad(
            "coverage", location,
            f"{over.size} real slot(s) not fetched exactly once, e.g. slot "
            f"{int(over[0])} fetched {int(covered[over[0]])} times"))
    slack_hit = np.nonzero(~valid & (covered > 0))[0]
    if slack_hit.size:
        findings.append(_bad(
            "sentinel-fetched", location,
            f"{slack_hit.size} sentinel slack slot(s) covered by a chunk, "
            f"e.g. slot {int(slack_hit[0])} — slack must keep the zero fill"))
    bad_sentinel = np.nonzero(~valid & (rs != n_rows))[0]
    if bad_sentinel.size:
        findings.append(_bad(
            "sentinel-value", location,
            f"slack slots must hold the sentinel {n_rows}, found "
            f"{int(rs[bad_sentinel[0]])} at slot {int(bad_sentinel[0])}"))
    return out, n_dma, findings


def _verify_cvmm_plan(plan: ops.CvmmPlan, n_rows: int,
                      location: str) -> Tuple[List[Finding], int]:
    """``drop_unheld`` plans pass too: their rows routed elsewhere sort last,
    count in no group and keep the slot ``M_pad`` (none)."""
    findings: List[Finding] = []
    perm = np.asarray(plan.perm)
    gs = np.asarray(plan.group_sizes)
    new_pos = np.asarray(plan.new_pos)
    te = np.asarray(plan.tile_expert)
    rs = np.asarray(plan.row_src)
    n_live = np.asarray(plan.n_live)
    gates = np.asarray(plan.gate_tiles).reshape(-1)
    m_all = perm.shape[0]
    m = int(gs.sum())                        # rows that get a slot
    e = gs.shape[0]
    if not np.array_equal(np.sort(perm), np.arange(m_all)):
        findings.append(_bad("perm", location,
                             "perm is not a permutation of the sorted rows"))
    if m > m_all or np.any(gs < 0):
        findings.append(_bad("group-sizes", location,
                             f"group_sizes sums to {m}, expected at most "
                             f"{m_all} non-negative rows"))
        m = m_all
    if np.any(new_pos[m:] != rs.shape[0]):
        findings.append(_bad("dropped-slot", location,
                             "rows in no group must keep the slot M_pad"))
    new_pos = new_pos[:m]
    if np.any(np.diff(te) < 0) or np.any(te < 0) or np.any(te >= e):
        findings.append(_bad("tile-expert", location,
                             "tile_expert must be non-decreasing within "
                             f"[0, {e}), got {te.tolist()}"))
    if n_live.shape != (1,) or not 0 <= int(n_live[0]) <= te.shape[0]:
        findings.append(_bad("live-bound", location,
                             f"n_live must be one count within [0, "
                             f"{te.shape[0]}], got {n_live.tolist()}"))
    elif np.any(rs[int(n_live[0]) * TM:] < n_rows):
        findings.append(_bad(
            "live-bound", location,
            f"a real slot sits at or past the live bound {int(n_live[0])}: "
            f"the kernels would skip its tile"))
    if np.unique(new_pos).shape[0] != m or np.any(new_pos < 0) \
            or np.any(new_pos >= rs.shape[0]):
        findings.append(_bad("new-pos", location,
                             "new_pos is not an injection of the sorted rows "
                             "into the padded slots"))
    else:
        # Each sorted row's slot must land in a tile owned by its expert —
        # otherwise the kernel would multiply it with the wrong weight block.
        row_e = np.repeat(np.arange(e), gs)
        slot_e = te[new_pos // TM]
        wrong = np.nonzero(slot_e != row_e)[0]
        if wrong.size:
            findings.append(_bad(
                "tile-purity", location,
                f"{wrong.size} sorted row(s) placed in a tile of another "
                f"expert, e.g. row {int(wrong[0])} (expert "
                f"{int(row_e[wrong[0]])}) in a tile of expert "
                f"{int(slot_e[wrong[0]])}"))
    slack_gates = gates[rs >= n_rows]
    if slack_gates.size and np.any(slack_gates != 0.0):
        findings.append(_bad("gate-slack", location,
                             "gate_tiles must be exactly 0 on slack slots "
                             "(that zero is what kills slack outputs)"))
    if int((rs < n_rows).sum()) != m:
        findings.append(_bad("row-src-count", location,
                             f"{int((rs < n_rows).sum())} real slots for {m} "
                             f"sorted rows"))
    return findings, 8


def _verify_gather_plan(plan: ops.GatherPlan, n_rows: int,
                        location: str) -> Tuple[List[Finding], int]:
    findings: List[Finding] = []
    rs = np.asarray(plan.row_src)
    tok = np.asarray(plan.tok_src)
    w = np.asarray(plan.weight_tiles).reshape(-1)
    valid = rs < n_rows
    m = int(valid.sum())
    if np.any(valid != (np.arange(rs.shape[0]) < m)):
        findings.append(_bad("slack-layout", location,
                             "GatherPlan keeps flat selection order: real "
                             "slots must form the prefix, slack the tail"))
    if np.any(tok[~valid] != tok.max(initial=0)) and np.any(valid):
        # slack tok_src is the n_tokens sentinel — must not scatter anywhere
        if np.any(tok[~valid] <= tok[valid].max(initial=-1)):
            findings.append(_bad("tok-slack", location,
                                 "slack slots carry a real destination token"))
    if np.any(w[~valid] != 0.0):
        findings.append(_bad("weight-slack", location,
                             "weight_tiles must be 0 on slack slots"))
    return findings, 3


def _verify_dedup_plan(plan: ops.DedupGatherPlan, n_rows: int,
                       location: str) -> Tuple[List[Finding], int]:
    findings: List[Finding] = []
    rs = np.asarray(plan.row_src)
    sel = np.asarray(plan.sel_pos)
    valid = rs < n_rows
    u = int(valid.sum())
    if np.any(valid != (np.arange(rs.shape[0]) < u)):
        findings.append(_bad("slack-layout", location,
                             "dedup row_src must keep the valid prefix "
                             "contiguous (sentinels sort last)"))
    prefix = rs[:u]
    if u and (np.any(np.diff(prefix) <= 0)):
        findings.append(_bad("sorted-unique", location,
                             "dedup row_src prefix must be strictly "
                             "ascending (sorted, duplicates collapsed)"))
    if np.any(sel < 0) or np.any(sel >= rs.shape[0]) \
            or (sel.size and np.any(~valid[sel])):
        findings.append(_bad("sel-pos-range", location,
                             "sel_pos must map every selection to a REAL "
                             "compacted slot (never sentinel slack)"))
    elif u and not np.array_equal(np.unique(sel), np.arange(u)):
        findings.append(_bad("sel-pos-surjective", location,
                             "every compacted row must be referenced by at "
                             "least one selection — an unreferenced row was "
                             "fetched for nothing, a missing one never "
                             "existed in the selection"))
    return findings, 4


def verify_plan(plan, n_rows: int, location: str = "") -> List[Finding]:
    """Every invariant of one plan provable without the original routing.

    The shared chunk-table replay plus the per-type structural checks; returns
    findings (empty = proven sound). The routing-aware cross-checks (plan
    fields vs the idx/gates that built them) live in ``check_plans``."""
    location = location or type(plan).__name__
    # arange "activations" make row identity visible to the replay compare
    x = np.arange(n_rows, dtype=np.int64).reshape(-1, 1)
    out, _, findings = replay_chunk_table(plan, n_rows, x, location)
    if out is not None:
        want = np.where((np.asarray(plan.row_src) < n_rows)[:, None],
                        np.asarray(plan.row_src)[:, None], 0)
        if not np.array_equal(out, want):
            findings.append(_bad(
                "gather-mismatch", location,
                "chunk-table replay does not reproduce take(row_src) with "
                "zero fill"))
    if isinstance(plan, ops.CvmmPlan):
        findings += _verify_cvmm_plan(plan, n_rows, location)[0]
    elif isinstance(plan, ops.GatherPlan):
        findings += _verify_gather_plan(plan, n_rows, location)[0]
    elif isinstance(plan, ops.DedupGatherPlan):
        findings += _verify_dedup_plan(plan, n_rows, location)[0]
    return findings


# ---------------------------------------------------------------------------
# The sweep: adversarial routings through every plan builder
# ---------------------------------------------------------------------------

# (name, n_tokens, n_experts_or_rows, k_or_s, style)
_MOE_CASES = (
    ("moe-random", 100, 6, 3, "random"),
    ("moe-skewed", 300, 3, 2, "skewed"),        # every row to expert 0
    ("moe-empty-experts", 57, 5, 2, "subset"),  # some experts get no rows
    ("moe-subtile", 8, 4, 2, "random"),         # n*k < TM
    ("moe-k1", 130, 2, 1, "random"),
    ("moe-held-share", 200, 2, 3, "held"),      # 2 of 8 experts held here
)
_GATHER_CASES = (
    ("gather-random", 40, 300, 4),
    ("gather-colliding", 100, 64, 8),           # heavy shared-row selection
    ("gather-sparse", 5, 1000, 3),
    ("gather-subtile", 3, 50, 2),
)
_EP_CASES = ((2, 256), (4, 128), (1, 384), (3, 64))
# (name, n_tokens, k, n_experts, d_model, expert_size) — the serving decode
# shape classes: tiny-M batches whose cached skeletons the engine reuses
_DECODE_CASES = (
    ("decode-b4", 4, 2, 4, 64, 32),
    ("decode-b8", 8, 2, 4, 64, 32),
    ("decode-b1-k1", 1, 1, 2, 64, 32),
    ("decode-b2-e8", 2, 2, 8, 128, 64),
)


def check_plans() -> Tuple[List[Finding], int]:
    import jax.numpy as jnp

    findings: List[Finding] = []
    checks = 0
    rng = np.random.RandomState(0)

    for name, n, e, k, style in _MOE_CASES:
        if style == "skewed":
            idx = np.zeros((n, k), np.int32)
        elif style == "subset":
            idx = rng.randint(0, max(e - 2, 1), size=(n, k)).astype(np.int32)
        elif style == "held":
            # ids 0..e-1 held here, e for the 6 experts held elsewhere
            idx = np.minimum(rng.randint(0, 4 * e, size=(n, k)),
                             e).astype(np.int32)
        else:
            idx = rng.randint(0, e, size=(n, k)).astype(np.int32)
        gates = rng.rand(n, k).astype(np.float32)
        plan = ops.make_moe_plan(jnp.asarray(idx), jnp.asarray(gates), n, e,
                                 drop_unheld=style == "held")
        findings += verify_plan(plan, n, name)
        checks += 10
        # routing cross-check: slot contents == the sorted selection
        held = int(np.asarray(plan.group_sizes).sum())
        perm = np.asarray(plan.perm)[:held]
        new_pos = np.asarray(plan.new_pos)[:held]
        tok = np.repeat(np.arange(n, dtype=np.int32), k)
        if not np.array_equal(np.asarray(plan.row_src)[new_pos], tok[perm]):
            findings.append(_bad(
                "routing-mismatch", name,
                "row_src[new_pos] != token of the sorted selection"))
        gexp = np.zeros((plan.m_pad,), np.float32)
        gexp[new_pos] = gates.reshape(-1)[perm]
        if not np.allclose(np.asarray(plan.gate_tiles).reshape(-1), gexp):
            findings.append(_bad(
                "gate-mismatch", name,
                "gate_tiles disagree with the routed gate values"))
        # the live bound is tight: every tile below it holds a row
        gs = np.bincount(idx.reshape(-1), minlength=e + 1)[:e]
        if int(np.asarray(plan.n_live)[0]) != int(np.sum(-(-gs // TM))):
            findings.append(_bad(
                "live-bound-tight", name,
                f"n_live {np.asarray(plan.n_live).tolist()} != "
                f"sum(ceil(group_size / {TM})) of the routing"))
        checks += 3

    for name, n, rows, s in _GATHER_CASES:
        idx = rng.randint(0, rows, size=(n, s)).astype(np.int32)
        w = rng.rand(n, s).astype(np.float32)
        gplan = ops.make_gather_plan(jnp.asarray(idx), jnp.asarray(w), rows)
        findings += verify_plan(gplan, rows, name)
        m = n * s
        if not np.array_equal(np.asarray(gplan.row_src)[:m], idx.reshape(-1)):
            findings.append(_bad("routing-mismatch", name,
                                 "GatherPlan row_src prefix != flat idx"))
        checks += 9

        dname = name.replace("gather", "dedup")
        dplan = ops.make_dedup_gather_plan(jnp.asarray(idx), jnp.asarray(w),
                                           rows)
        findings += verify_plan(dplan, rows, dname)
        sel = np.asarray(dplan.sel_pos)
        if not np.array_equal(np.asarray(dplan.row_src)[sel], idx.reshape(-1)):
            findings.append(_bad(
                "indirection-mismatch", dname,
                "row_src[sel_pos] must reproduce the flat selection — the "
                "scatter-side weighting depends on it"))
        if not np.array_equal(np.asarray(dplan.tok_src),
                              np.repeat(np.arange(n, dtype=np.int32), s)):
            findings.append(_bad("tok-src", dname,
                                 "dedup tok_src != flat selection tokens"))
        checks += 10

    from ..core import dispatch
    for e_local, cap_g in _EP_CASES:
        plan = dispatch.ep_local_plan(e_local, cap_g)
        findings += verify_plan(plan, e_local * cap_g,
                                f"ep e_local={e_local} cap_g={cap_g}")
        checks += 10

    # Decode skeletons: the routing-free layout must assemble into a plan
    # that passes the SAME oracle as every per-call plan, for any routing —
    # otherwise the engine's cached-skeleton shortcut could drift silently.
    for name, n, k, e, d_model, esz in _DECODE_CASES:
        skel = ops.make_decode_plan(n, k, e, d_model, esz)
        if skel is None:
            findings.append(_bad(
                "decode-no-tile", name,
                f"no fitting tile for n={n} k={k} e={e} d={d_model} "
                f"g={esz} — the decode shape classes must stay servable"))
            continue
        # the skeleton's dedup token gather is a plan in its own right
        findings += verify_plan(skel.gather, n, f"{name}/gather")
        te_want = np.repeat(np.arange(e, dtype=np.int32), skel.cap // TM)
        if not np.array_equal(np.asarray(skel.tile_expert), te_want):
            findings.append(_bad(
                "decode-tile-expert", name,
                "skeleton tile_expert != repeat(arange(e), cap//TM) — the "
                "static expert layout is what makes the cache routing-free"))
        checks += 11
        idx = rng.randint(0, e, size=(n, k)).astype(np.int32)
        gates = rng.rand(n, k).astype(np.float32)
        full = ops.assemble_decode_plan(skel, jnp.asarray(idx),
                                        jnp.asarray(gates))
        findings += verify_plan(full, n, name)
        perm = np.asarray(full.perm)
        new_pos = np.asarray(full.new_pos)
        tok = np.repeat(np.arange(n, dtype=np.int32), k)
        if not np.array_equal(np.asarray(full.row_src)[new_pos], tok[perm]):
            findings.append(_bad(
                "routing-mismatch", name,
                "assembled row_src[new_pos] != token of the sorted selection"))
        gexp = np.zeros((full.m_pad,), np.float32)
        gexp[new_pos] = gates.reshape(-1)[perm]
        if not np.allclose(np.asarray(full.gate_tiles).reshape(-1), gexp):
            findings.append(_bad(
                "gate-mismatch", name,
                "assembled gate_tiles disagree with the routed gate values"))
        if not np.array_equal(np.asarray(full.tile_expert),
                              np.asarray(skel.tile_expert)):
            findings.append(_bad(
                "decode-tile-drift", name,
                "assembled plan's tile_expert differs from the skeleton's — "
                "the cached GEMM layout would not match the materialized one"))
        slots = np.asarray(ops.decode_slots(skel, jnp.asarray(idx)))
        if not np.array_equal(np.sort(new_pos), np.sort(slots)):
            findings.append(_bad(
                "decode-slot-mismatch", name,
                "decode_slots() and the assembled new_pos place selections "
                "in different padded rows"))
        checks += 14
    return findings, checks
