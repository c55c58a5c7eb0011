"""jit-ready CVMM wrapper: layout plan + backend dispatch + custom_vjp.

Backends
--------
"pallas"        The TPU kernels (cvmm.py), unfused: rows are gathered/sorted at
                the XLA level, each grouped GEMM is one pallas_call. On CPU the
                kernels run in interpret mode — used by the tests.
"pallas_fused"  The fused pipeline: one ``CvmmPlan`` computed per MoE call, a
                streamed gather-fused w1 kernel (activations stay in HBM and
                double-buffer through VMEM row tile by row tile — any token
                count) with activation/GLU epilogue and a w2 kernel with the
                gate multiply fused in. The plan is threaded through forward
                and backward via custom_vjp residuals — no layout recompute,
                no re-pad in backward, and the backward is gather-free at the
                HBM level: dW/dX stream their unsorted operands through the
                same run-batched row-DMA pipeline instead of materializing
                tile-aligned copies. Exposed at the MoE-MLP granularity via
                ``moe_mlp_fused``; for the bare ``cvmm`` API it degrades to
                the planned unfused path (a single GEMM has no epilogue to
                fuse).
"ragged"        jax.lax.ragged_dot — XLA's grouped matmul; differentiable; the
                default on CPU and a correctness cross-check on TPU.
"ref"           Pure-jnp one-hot oracle (kernels/ref.py), O(N*E) — tests only.

The public ``cvmm(x, group_sizes, w)`` takes rows already *sorted by expert*
(group_sizes sums to rows) and returns x[i] @ w[expert(i)].

Layout plans
------------
``CvmmPlan`` (see kernels/cvmm.py for the field contract) is computed ONCE per
MoE call by ``make_moe_plan`` and reused by every kernel launch of that call,
forward and backward. ``_tile_layout`` is the single source of the tile-aligned
layout math; nothing recomputes it downstream of a plan.

``GatherPlan`` (``make_gather_plan`` + ``gathered_weighted_sum``) is the
expert_size-1 degenerate for the framework's weighted value aggregation —
PKM values, top-K W2 rows (core/dispatch.weighted_value_sum): no grouped
GEMM, only the run-batched streamed row-DMA gather with a fused per-row
weight epilogue and the scatter back to tokens. Shares ``_plan_runs`` and
the custom_vjp plan-threading discipline with the MoE pipeline.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import dtypes

from ..common import act_fn, round_up
from . import autotune
from . import cvmm as cvmm_mod
from . import ref as refk
from .cvmm import (FUSIBLE_ACTIVATIONS, LANE, TM, _RUN_SIZES,
                   cvmm_dw_pallas, cvmm_dw_streamed_pallas,
                   cvmm_fused_w1_pallas, cvmm_fused_w2_pallas,
                   cvmm_gather_rows_pallas, cvmm_pallas,
                   gather_tile_fits)

_FORCED_IMPL: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> None:
    global _FORCED_IMPL
    _FORCED_IMPL = impl


def default_impl() -> str:
    if _FORCED_IMPL:
        return _FORCED_IMPL
    return "pallas_fused" if jax.default_backend() == "tpu" else "ragged"


def _impl_interpret(impl: str) -> bool:
    return impl.endswith("_interpret") or jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Tile-aligned layout plan (megablocks-style)
# ---------------------------------------------------------------------------

class CvmmPlan(NamedTuple):
    """One-per-MoE-call layout metadata shared by all kernel launches.

    Field contract documented in kernels/cvmm.py. ``m_pad`` is static:
    ``tile_expert.shape[0] * TM``. All int fields get float0 cotangents;
    ``gate_tiles`` is the one differentiable leaf (grads flow back to routing).
    """
    perm: jax.Array          # (N*K,) argsort of flat expert ids (stable)
    group_sizes: jax.Array   # (E,) rows per expert
    new_pos: jax.Array       # (N*K,) tile-aligned slot of sorted row i
    row_src: jax.Array       # (M_pad,) source token row; sentinel N on slack
    run_start: jax.Array     # (M_pad,) per-tile DMA chunk table (compacted):
    run_len: jax.Array       #   entry j of tile t (flat t*TM+j) copies
                             #   run_len[j] consecutive rows starting at
                             #   row_src[t*TM + run_start[j]] into tile slots
                             #   [run_start[j], +run_len[j]); 0 = unused.
                             #   Lengths are static power-of-two classes
                             #   (see _plan_runs / cvmm._RUN_SIZES).
    run_off: jax.Array       # (M_pad//TM * 9,) per-tile size-class boundaries
                             #   into that table: class ci's chunks sit at
                             #   entries [run_off[t*9+ci], run_off[t*9+ci+1])
                             #   — lets kernels loop per static class with no
                             #   per-entry size dispatch.
    tile_expert: jax.Array   # (M_pad//TM,) row-tile -> expert id
    n_live: jax.Array        # (1,) int32 live bound: tiles [0, n_live) hold
                             #   every row; kernels skip the tiles past it
    gate_tiles: jax.Array    # (M_pad//TM, TM) float32 gate per slot, 0 on slack

    @property
    def m_pad(self) -> int:
        return self.tile_expert.shape[0] * TM


def grid_tiles(m: int, e: int) -> int:
    """Row tiles of the static layout bound for ``m`` rows over ``e``
    experts: m rounded up to TM, plus one tile of padding per expert."""
    return round_up(m, TM) // TM + e


def live_tiles(group_sizes: jax.Array) -> jax.Array:
    """(1,) int32: the row tiles that hold rows, sum(ceil(gs / TM)). The
    layout packs them first, so every tile at or past this count is slack.
    The plan's ``n_live`` and the step's ``moe_tile_share`` both read it."""
    gs = group_sizes.astype(jnp.int32)
    return jnp.sum((gs + TM - 1) // TM, keepdims=True)


def _tile_layout(group_sizes: jax.Array, m: int, e: int):
    """Map sorted rows to a layout where each expert's range is TM-aligned.

    Returns (new_pos (m,), tile_expert (m_pad//TM,), n_live (1,), m_pad).
    m_pad is a static upper bound m + e*TM; slack tiles are all-zero, sit
    at or past ``n_live``, and are clamped to the last expert.
    """
    gs = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)])[:-1]
    ps = ((gs + TM - 1) // TM) * TM                       # padded group sizes
    offs_p = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(ps)])[:-1]
    rows = jnp.arange(m, dtype=jnp.int32)
    re = refk.row_experts(gs, m).astype(jnp.int32)
    new_pos = offs_p[re] + (rows - offs[re])
    n_tiles = grid_tiles(m, e)
    ends_p = jnp.cumsum(ps)
    tile_expert = jnp.searchsorted(ends_p, jnp.arange(n_tiles, dtype=jnp.int32) * TM,
                                   side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, e - 1)         # clamp slack tiles
    return new_pos, tile_expert, live_tiles(gs), n_tiles * TM


def _plan_runs(row_src: jax.Array, n_rows: int):
    """Batch each tile's maximal contiguous ``row_src`` runs into DMA chunks.

    Returns (run_start, run_len, run_off). run_start/run_len are (M_pad,)
    int32: entry j of tile t (flat index t*TM + j) describes one HBM->VMEM
    copy of ``run_len[t*TM+j]`` consecutive source rows starting at
    ``row_src[t*TM + run_start[t*TM+j]]`` into the tile's slot range
    [run_start, run_start + run_len). DMA copy shapes must be static, so each
    maximal run is greedily decomposed into power-of-two chunks (the kernels
    predicate on ``cvmm._RUN_SIZES``): a fully contiguous tile is ONE
    descriptor, an isolated row is one size-1 descriptor — never more chunks
    than the old one-DMA-per-row scheme. ``run_len == 0`` marks unused
    entries; slack slots (sentinel ``row_src``) belong to no chunk and keep
    the kernels' zero fill.

    Each tile's chunk entries are grouped by size class (largest first, source
    order preserved within a class, unused entries last), and ``run_off``
    ((M_pad//TM)*(len(_RUN_SIZES)+1),) int32 carries the per-tile class
    boundaries: class ci's chunks occupy entries [run_off[t*C+ci],
    run_off[t*C+ci+1]) with C = len(_RUN_SIZES)+1. The kernels therefore run
    one dynamic-bound loop per STATIC size class — total iterations == #chunks
    — instead of dispatching on run_len per entry (run_len itself is kept in
    the plan for tests/telemetry; the kernels never read it)."""
    src = row_src.reshape(-1, TM).astype(jnp.int32)
    n_tiles = src.shape[0]
    valid = src < n_rows
    slots = jnp.arange(TM, dtype=jnp.int32)[None, :]
    prev_valid = jnp.pad(valid[:, :-1], ((0, 0), (1, 0)))
    prev_src = jnp.pad(src[:, :-1], ((0, 0), (1, 0)))
    contig = valid & prev_valid & (src == prev_src + 1)
    is_start = valid & ~contig
    is_end = valid & jnp.pad(~contig[:, 1:], ((0, 0), (0, 1)),
                             constant_values=True)
    start_pos = jax.lax.cummax(jnp.where(is_start, slots, -1), axis=1)
    end_pos = jax.lax.cummin(jnp.where(is_end, slots, TM), axis=1,
                             reverse=True)
    length = jnp.where(valid, end_pos - start_pos + 1, 0)
    off = slots - start_pos
    # Greedy power-of-two decomposition: a run of length L gets a chunk of
    # size 2^b at in-run offset (L >> (b+1)) << (b+1) for each set bit b.
    # cclass = index into the descending cvmm._RUN_SIZES (0 = size TM);
    # non-chunk slots get the sentinel class nc so argsort pushes them last.
    nc = len(_RUN_SIZES)
    csize = jnp.zeros_like(src)
    cclass = jnp.full_like(src, nc)
    for b in range(TM.bit_length()):
        chunk_off = (length >> (b + 1)) << (b + 1)
        sel = valid & ((length & (1 << b)) > 0) & (off == chunk_off)
        csize = jnp.where(sel, 1 << b, csize)
        cclass = jnp.where(sel, nc - 1 - b, cclass)
    order = jnp.argsort(cclass, axis=1, stable=True).astype(jnp.int32)
    run_len = jnp.take_along_axis(csize, order, axis=1)
    counts = jnp.sum(cclass[:, :, None] == jnp.arange(nc)[None, None, :],
                     axis=1)
    run_off = jnp.concatenate(
        [jnp.zeros((n_tiles, 1), jnp.int32),
         jnp.cumsum(counts, axis=1).astype(jnp.int32)], axis=1)
    return order.reshape(-1), run_len.reshape(-1), run_off.reshape(-1)


def make_moe_plan(idx: jax.Array, gates: jax.Array, n_tokens: int,
                  n_experts: int, drop_unheld: bool = False) -> CvmmPlan:
    """Build the CvmmPlan for one MoE call from the routing selection.

    idx (N, K) int expert ids, gates (N, K) gate values. Differentiable in
    ``gates`` (the scatter into ``gate_tiles`` is transparent to autodiff).
    ``drop_unheld``: ids may also be ``n_experts``, a row routed to an expert
    this layer does not hold; such rows sort last, join no group and get no
    slot (row_src keeps the sentinel there). Either way ``n_live`` counts
    the tiles of the rows that got slots."""
    k = idx.shape[-1]
    e_flat = idx.reshape(-1).astype(jnp.int32)
    g_flat = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(n_tokens, dtype=jnp.int32), k)
    perm = jnp.argsort(e_flat, stable=True)
    group_sizes = jnp.bincount(e_flat, length=n_experts).astype(jnp.int32)
    new_pos, tile_expert, n_live, m_pad = _tile_layout(
        group_sizes, e_flat.shape[0], n_experts)
    if drop_unheld:
        new_pos = jnp.where(e_flat[perm] < n_experts, new_pos, m_pad)
    row_src = jnp.full((m_pad,), n_tokens, jnp.int32).at[new_pos].set(tok[perm])
    run_start, run_len, run_off = _plan_runs(row_src, n_tokens)
    gate_pad = jnp.zeros((m_pad,), jnp.float32).at[new_pos].set(
        g_flat[perm].astype(jnp.float32))
    return CvmmPlan(perm=perm, group_sizes=group_sizes, new_pos=new_pos,
                    row_src=row_src, run_start=run_start, run_len=run_len,
                    run_off=run_off, tile_expert=tile_expert, n_live=n_live,
                    gate_tiles=gate_pad.reshape(m_pad // TM, TM))


def plan_dma_stats(plan, n_rows: int, *, verify: bool = False) -> dict:
    """Telemetry: one plan's gather-DMA descriptor counts — run-batched chunks
    (what each streamed kernel pass issues, ``run_len > 0`` entries) vs the
    retired one-copy-per-row scheme, plus a per-size-class chunk histogram
    (``chunk_hist``: descriptor count per ``cvmm._RUN_SIZES`` class — shows
    whether packing ever reaches the large classes, not just the totals).

    Accepts any plan carrying ``row_src``/``run_len`` (CvmmPlan, GatherPlan,
    DedupGatherPlan). For a ``DedupGatherPlan`` the per-row baseline is the
    PRE-dedup selection count (one DMA per selected (token, slot) — what the
    flat GatherPlan would issue without run luck), so ``batching_factor``
    reports the full dedup+coalescing win; ``unique_rows`` records the
    post-dedup row count separately.

    ``verify=True`` additionally runs the plan through the static invariant
    oracle (repro.analysis.plans — the same checks CI's analysis gate applies)
    and raises ``ValueError`` on any violation, so benchmarks and property
    suites reporting stats on a plan prove its chunk table sound in the same
    call."""
    if verify:
        from ..analysis.plans import verify_plan
        findings = verify_plan(plan, n_rows)
        if findings:
            raise ValueError("plan invariant violations:\n" + "\n".join(
                f"  [{f.check}] {f.detail}" for f in findings))
    run_len = np.asarray(plan.run_len)
    batched = int((run_len > 0).sum())
    stats = {"chunk_hist": {str(int(s)): int((run_len == s).sum())
                            for s in _RUN_SIZES}}
    if isinstance(plan, DedupGatherPlan):
        per_row = int(plan.sel_pos.shape[0])
        stats["unique_rows"] = int((np.asarray(plan.row_src) < n_rows).sum())
    else:
        per_row = int((np.asarray(plan.row_src) < n_rows).sum())
    stats.update(per_row=per_row, run_batched=batched,
                 batching_factor=round(per_row / max(batched, 1), 3))
    return stats


def _float0(a: jax.Array):
    return np.zeros(a.shape, dtypes.float0)


def _pad_lane(a: jax.Array, axis: int) -> jax.Array:
    size = a.shape[axis]
    pad = round_up(size, LANE) - size
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _pad_w(w: jax.Array) -> jax.Array:
    return _pad_lane(_pad_lane(w, 1), 2)


def _mask_empty(dw: jax.Array, group_sizes: jax.Array) -> jax.Array:
    # Blocks of experts with zero rows are never visited by the dW kernel
    # (their padded group has no live tile) and stay uninitialized.
    return jnp.where((group_sizes > 0)[:, None, None], dw, 0.0)


# ---------------------------------------------------------------------------
# Weighted row-gather plan (the framework's shared retrieval+aggregation
# primitive: PKM value lookup and the top-K MLP's sparse down-projection)
# ---------------------------------------------------------------------------

class GatherPlan(NamedTuple):
    """Layout metadata for one planned weighted row gather-sum.

    The expert_size-1 degenerate of ``CvmmPlan``: each selected "expert" is a
    single row of a value table (PKM values, W2 rows), so there is no grouped
    GEMM and no expert-pure tiling — only the run-batched streamed row-DMA
    pipeline, a per-slot weight, and the scatter back to tokens. Slots are in
    flat (token, slot) order padded to a TM multiple; the table is shared by
    forward and backward (custom_vjp residuals — no layout recompute). All
    int fields get float0 cotangents; ``weight_tiles`` is the one
    differentiable leaf (grads flow back to the selection scores)."""
    row_src: jax.Array       # (M_pad,) source row in the value table;
                             #   sentinel n_rows on slack slots
    tok_src: jax.Array       # (M_pad,) destination token of each slot;
                             #   sentinel n_tokens on slack
    run_start: jax.Array     # (M_pad,) per-tile DMA chunk table — same
    run_len: jax.Array       #   contract as CvmmPlan (ops._plan_runs)
    run_off: jax.Array       # (M_pad//TM * 9,) per-tile size-class bounds
    weight_tiles: jax.Array  # (M_pad//TM, TM) float32 weight per slot, 0 on
                             #   slack — fused into the gather epilogue

    @property
    def m_pad(self) -> int:
        return self.weight_tiles.shape[0] * TM


def make_gather_plan(idx: jax.Array, weights: jax.Array,
                     n_rows: int) -> GatherPlan:
    """Build the GatherPlan for one weighted aggregation call.

    idx (N, S) int row ids into a value table of ``n_rows`` rows, weights
    (N, S) aggregation weights. Differentiable in ``weights``. Slots keep the
    flat (token, s) order — no sort: there is no per-expert weight block to
    amortize, and the run batching still collapses whatever contiguity the
    selection happens to have."""
    n_tokens, s = idx.shape
    m = n_tokens * s
    m_pad = round_up(m, TM)
    row_src = jnp.pad(idx.reshape(-1).astype(jnp.int32), (0, m_pad - m),
                      constant_values=n_rows)
    tok_src = jnp.pad(jnp.repeat(jnp.arange(n_tokens, dtype=jnp.int32), s),
                      (0, m_pad - m), constant_values=n_tokens)
    run_start, run_len, run_off = _plan_runs(row_src, n_rows)
    w_pad = jnp.pad(weights.reshape(-1).astype(jnp.float32), (0, m_pad - m))
    return GatherPlan(row_src=row_src, tok_src=tok_src, run_start=run_start,
                      run_len=run_len, run_off=run_off,
                      weight_tiles=w_pad.reshape(m_pad // TM, TM))


def gather_supported(d_model: int, dtype=jnp.float32) -> bool:
    """Gate for the planned weighted-gather path: tile-level residency only.

    Mirrors ``fused_supported``/``pallas_supported`` for the streamed gather
    kernel — the value-table row count and the selection size never appear
    (both live in HBM); only a feature dim whose (TM, d_pad) tile working set
    cannot fit VMEM falls back to the XLA take+einsum rung."""
    return gather_tile_fits(round_up(d_model, LANE),
                            jnp.dtype(dtype).itemsize)


def _gws_impl(static, values_pad, row_src, tok_src, run_start, run_off,
              weight_tiles):
    n_tokens, fuse_weights, interpret, n_buffers = static
    if fuse_weights:
        rows = cvmm_gather_rows_pallas(values_pad, row_src, run_start, run_off,
                                       weight_tiles, interpret=interpret,
                                       n_buffers=n_buffers)
    else:
        # unfused rung: bare streamed gather, weight multiply at the XLA level
        rows = cvmm_gather_rows_pallas(values_pad, row_src, run_start, run_off,
                                       interpret=interpret,
                                       n_buffers=n_buffers)
        rows = (rows.astype(jnp.float32)
                * weight_tiles.reshape(-1)[:, None]).astype(rows.dtype)
    out = jnp.zeros((n_tokens, values_pad.shape[1]), rows.dtype)
    # slack slots carry the sentinel token — out of bounds, dropped here.
    return out.at[tok_src].add(rows, mode="drop")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gathered_weighted_sum(static, values_pad, row_src, tok_src, run_start,
                           run_off, weight_tiles):
    return _gws_impl(static, values_pad, row_src, tok_src, run_start, run_off,
                     weight_tiles)


def _gws_fwd(static, values_pad, row_src, tok_src, run_start, run_off,
             weight_tiles):
    y = _gws_impl(static, values_pad, row_src, tok_src, run_start, run_off,
                  weight_tiles)
    return y, (values_pad, row_src, tok_src, run_start, run_off, weight_tiles)


def _gws_bwd(static, res, dy):
    _, _, interpret, n_buffers = static
    values_pad, row_src, tok_src, run_start, run_off, weight_tiles = res
    w_flat = weight_tiles.reshape(-1)
    # Per-slot cotangent rows: sentinel tokens (slack) zero-fill.
    dy_rows = jnp.take(dy, tok_src, axis=0, mode="fill", fill_value=0)
    # dweight[s] = dy[tok[s]] . values[row_src[s]]: re-stream the un-weighted
    # gather through the same plan (the fused forward never materialized it).
    g = cvmm_gather_rows_pallas(values_pad, row_src, run_start, run_off,
                                interpret=interpret, n_buffers=n_buffers)
    dweights = jnp.sum(g.astype(jnp.float32) * dy_rows.astype(jnp.float32),
                       axis=1)
    dvalues = jnp.zeros_like(values_pad).at[row_src].add(
        (dy_rows.astype(jnp.float32) * w_flat[:, None]).astype(
            values_pad.dtype), mode="drop")
    return (dvalues, _float0(row_src), _float0(tok_src), _float0(run_start),
            _float0(run_off), dweights.reshape(weight_tiles.shape))


_gathered_weighted_sum.defvjp(_gws_fwd, _gws_bwd)


def gathered_weighted_sum(values: jax.Array, plan: GatherPlan, n_tokens: int,
                          *, fuse_weights: bool = True,
                          interpret: Optional[bool] = None,
                          n_buffers: Optional[int] = None) -> jax.Array:
    """Planned weighted row gather-sum: y[t] = sum_{s: tok[s]=t} w[s] * V[row[s]].

    The framework's shared retrieval+aggregation primitive executed through
    the streamed row-DMA pipeline: the value table stays unsorted in HBM
    (``pl.ANY``) and double-buffers (TM, d) row tiles through VMEM, so no
    (N, S, d) dense value gather is ever materialized at the XLA level. PKM
    value aggregation (V = the (n_values, d) value table, S = H*K) and the
    top-K MLP's sparse down-projection (V = W2 rows, S = K) both lower here
    via core/dispatch.weighted_value_sum. ``fuse_weights=False`` is the
    unfused rung: same streamed gather, weight multiply as an XLA pass.
    ``n_buffers`` (gather pipeline depth) is resolved through the tuner when
    omitted — depth 2 unless a tuned cache says deeper wins."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = values.shape[-1]
    if n_buffers is None:
        dec = autotune.gather_tiles(round_up(d, LANE),
                                    jnp.dtype(values.dtype).itemsize,
                                    budget=cvmm_mod.VMEM_BUDGET)
        n_buffers = dec.tiles["n_buffers"] if dec.tiles is not None else None
    y = _gathered_weighted_sum((n_tokens, fuse_weights, interpret, n_buffers),
                               _pad_lane(values, 1), plan.row_src,
                               plan.tok_src, plan.run_start, plan.run_off,
                               plan.weight_tiles)
    return y[:, :d]


# ---------------------------------------------------------------------------
# Deduplicated, value-index-sorted gather plan (the coalescing strategy:
# million-value PKM / shared-row selections)
# ---------------------------------------------------------------------------

class DedupGatherPlan(NamedTuple):
    """Layout metadata for one DEDUPLICATED weighted row gather-sum.

    Where ``GatherPlan`` keeps slots in flat (token, slot) order — one DMA
    slot per selection, shared rows copied once per selecting token — this
    plan is built from the value-index-SORTED UNION of the batch's
    selections: every row the batch touches appears exactly once, in
    ascending row order. Co-selected rows collapse to one DMA and adjacent
    value indices become real contiguous runs for ``_plan_runs`` to pack
    into multi-row descriptors, so the compacted block streams HBM->VMEM
    once regardless of how many tokens share it. Per-token weighting moves
    to a scatter-side index indirection: ``sel_pos`` maps each flat
    (token, slot) selection to its compacted slot, ``tok_src``/``weights``
    carry the destination token and weight. All int fields get float0
    cotangents; ``weights`` is the one differentiable leaf."""
    row_src: jax.Array    # (U_pad,) SORTED unique value rows; ascending,
                          #   sentinel n_rows on slack (sorts last, so the
                          #   valid prefix stays contiguous)
    run_start: jax.Array  # (U_pad,) per-tile DMA chunk table — same contract
    run_len: jax.Array    #   as CvmmPlan/GatherPlan (ops._plan_runs);
                          #   run_len is telemetry only
    run_off: jax.Array    # (U_pad//TM * 9,) per-tile size-class bounds
    sel_pos: jax.Array    # (M,) compacted slot of flat selection (token, s):
                          #   row_src[sel_pos[t*S+s]] == idx[t, s]
    tok_src: jax.Array    # (M,) destination token of each flat selection
    weights: jax.Array    # (M,) float32 per-selection weight — applied in
                          #   the scatter epilogue, not fused into the gather

    @property
    def u_pad(self) -> int:
        return self.row_src.shape[0]


def make_dedup_gather_plan(idx: jax.Array, weights: jax.Array,
                           n_rows: int) -> DedupGatherPlan:
    """Build the dedup/sorted plan for one weighted aggregation call.

    idx (N, S) int row ids into a value table of ``n_rows`` rows, weights
    (N, S) aggregation weights. Differentiable in ``weights``. The unique
    set is computed at a STATIC size (jit-safe): at most min(N*S, n_rows)
    distinct rows can exist, the remainder is sentinel slack. ``jnp.unique``
    returns the uniques ascending with the fill value appended at the end,
    which is exactly the sorted-prefix + sentinel-tail layout ``_plan_runs``
    wants."""
    n_tokens, s = idx.shape
    m = n_tokens * s
    u_cap = min(m, n_rows)
    u_pad = round_up(u_cap, TM)
    flat = idx.reshape(-1).astype(jnp.int32)
    uniq, inv = jnp.unique(flat, size=u_cap, fill_value=n_rows,
                           return_inverse=True)
    row_src = jnp.pad(uniq.astype(jnp.int32), (0, u_pad - u_cap),
                      constant_values=n_rows)
    run_start, run_len, run_off = _plan_runs(row_src, n_rows)
    tok_src = jnp.repeat(jnp.arange(n_tokens, dtype=jnp.int32), s)
    return DedupGatherPlan(row_src=row_src, run_start=run_start,
                           run_len=run_len, run_off=run_off,
                           sel_pos=inv.reshape(-1).astype(jnp.int32),
                           tok_src=tok_src,
                           weights=weights.reshape(-1).astype(jnp.float32))


def _gws_dedup_impl(static, values_pad, row_src, run_start, run_off, sel_pos,
                    tok_src, weights):
    n_tokens, interpret, n_buffers = static
    # One streamed pass over the COMPACTED block: U_pad slots, not M.
    rows = cvmm_gather_rows_pallas(values_pad, row_src, run_start, run_off,
                                   interpret=interpret, n_buffers=n_buffers)
    # Scatter-side indirection: expand compacted rows back to per-selection
    # rows (a (M,)-index take, feature-dim cheap vs the HBM row traffic the
    # dedup saved), weight, and scatter-add to tokens.
    sel_rows = jnp.take(rows, sel_pos, axis=0)             # (M, d_pad)
    wrows = (sel_rows.astype(jnp.float32) * weights[:, None]).astype(rows.dtype)
    out = jnp.zeros((n_tokens, values_pad.shape[1]), rows.dtype)
    return out.at[tok_src].add(wrows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gathered_weighted_sum_dedup(static, values_pad, row_src, run_start,
                                 run_off, sel_pos, tok_src, weights):
    return _gws_dedup_impl(static, values_pad, row_src, run_start, run_off,
                           sel_pos, tok_src, weights)


def _gws_dedup_fwd(static, values_pad, row_src, run_start, run_off, sel_pos,
                   tok_src, weights):
    y = _gws_dedup_impl(static, values_pad, row_src, run_start, run_off,
                        sel_pos, tok_src, weights)
    return y, (values_pad, row_src, run_start, run_off, sel_pos, tok_src,
               weights)


def _gws_dedup_bwd(static, res, dy):
    _, interpret, n_buffers = static
    values_pad, row_src, run_start, run_off, sel_pos, tok_src, weights = res
    dy_rows = jnp.take(dy, tok_src, axis=0)                # (M, d_pad)
    # dweight[s] = dy[tok[s]] . V[idx[s]]: re-stream the compacted gather
    # through the same plan and expand via the indirection (the forward never
    # materialized the per-selection rows).
    rows = cvmm_gather_rows_pallas(values_pad, row_src, run_start, run_off,
                                   interpret=interpret, n_buffers=n_buffers)
    dweights = jnp.sum(jnp.take(rows, sel_pos, axis=0).astype(jnp.float32)
                       * dy_rows.astype(jnp.float32), axis=1)
    # dV two-level scatter: selections first accumulate into the COMPACTED
    # block (collisions only among tokens sharing a row), then the compacted
    # block scatters to the table — sentinel slack rows drop, and each table
    # row receives exactly one contribution.
    dcompact = jnp.zeros((row_src.shape[0], values_pad.shape[1]), jnp.float32
                         ).at[sel_pos].add(
        dy_rows.astype(jnp.float32) * weights[:, None])
    dvalues = jnp.zeros_like(values_pad).at[row_src].add(
        dcompact.astype(values_pad.dtype), mode="drop")
    return (dvalues, _float0(row_src), _float0(run_start), _float0(run_off),
            _float0(sel_pos), _float0(tok_src), dweights)


_gathered_weighted_sum_dedup.defvjp(_gws_dedup_fwd, _gws_dedup_bwd)


def gathered_weighted_sum_dedup(values: jax.Array, plan: DedupGatherPlan,
                                n_tokens: int, *,
                                interpret: Optional[bool] = None,
                                n_buffers: Optional[int] = None) -> jax.Array:
    """Planned weighted row gather-sum over the DEDUPLICATED selection union.

    Same contract as ``gathered_weighted_sum`` — y[t] = sum_s w[t,s] *
    V[idx[t,s]] — but the streamed pass covers each selected row ONCE (sorted
    ascending, so ``_plan_runs`` packs adjacent value indices into multi-row
    descriptors) and the per-token weights apply through the plan's
    scatter-side indirection. This is the production path for shared-row
    selections (PKM value aggregation: hot values are co-selected across the
    batch); at 1M+ values the HBM row traffic is the whole cost and dedup
    bounds it by min(N*S, rows-actually-touched). ``n_buffers`` resolves
    through the tuner's dedup-gather shape class when omitted."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = values.shape[-1]
    if n_buffers is None:
        dec = autotune.dedup_gather_tiles(round_up(d, LANE),
                                          jnp.dtype(values.dtype).itemsize,
                                          budget=cvmm_mod.VMEM_BUDGET)
        n_buffers = dec.tiles["n_buffers"] if dec.tiles is not None else None
    y = _gathered_weighted_sum_dedup((n_tokens, interpret, n_buffers),
                                     _pad_lane(values, 1), plan.row_src,
                                     plan.run_start, plan.run_off,
                                     plan.sel_pos, plan.tok_src, plan.weights)
    return y[:, :d]


# ---------------------------------------------------------------------------
# Tile decisions (one resolution per plan, threaded through custom_vjp)
# ---------------------------------------------------------------------------
# Each planned execution resolves its tile choices ONCE — at plan/dispatch
# time, through the tuner (kernels/autotune.py) against the call-time
# cvmm.VMEM_BUDGET — and threads them into every kernel launch of that call,
# forward and backward, as a hashable static argument. The kernels never
# re-query; "does any tile fit" (the capability gates below) and "which tile"
# are literally the same answer. Tiles stay OUT of the plan NamedTuples: plan
# fields are pytree leaves (traced under jit), tiles must stay static ints.

class FusedTiles(NamedTuple):
    """Static tile choices for one fused MoE-MLP call (fwd + bwd kernels)."""
    w1_tn: int        # fused w1, inference (single output)
    w1_train_tn: int  # fused w1 under vjp (writes preactivations too)
    t0_tn: int        # backward's gather(dy) @ w2^T streamed GEMM
    w2_tn: int        # w2 gate-epilogue fwd; also dX bwd (same shape key)
    dw_tb: int        # streamed dW blocked-width tile (dW1/dW1g/dW2 share it)
    w1_nb: int        # gather pipeline depths per streamed kernel; every
    w1_train_nb: int  # launch pairs a width with the depth from the SAME
    t0_nb: int        # tuner decision — mixing (w1_train_tn, w1_nb) was a
    dw_nb: int        # combination neither decision proved fits VMEM
    provenance: str   # "heuristic" | "tuned" (any constituent tuned -> tuned)


class PlannedTiles(NamedTuple):
    """Static tile choices for one planned unfused grouped GEMM (fwd + bwd)."""
    fwd_tn: int       # x @ w
    dx_tn: int        # g @ w^T
    dw_tk: int        # dW outer-product K tile
    dw_tn: int        # dW outer-product N tile
    provenance: str


def _merge_prov(*decisions) -> str:
    return ("tuned" if any(d.provenance == "tuned" for d in decisions)
            else "heuristic")


def fused_mlp_tiles(d_model: int, expert_size: int, dtype=jnp.float32,
                    glu: bool = False) -> Optional[FusedTiles]:
    """Resolve every tile the fused pipeline will launch (forward AND
    backward) for one shape class, or None when some kernel has no fitting
    tile. Reads ``cvmm.VMEM_BUDGET`` at call time (tests monkeypatch it)."""
    d_pad, g_pad = round_up(d_model, LANE), round_up(expert_size, LANE)
    b = jnp.dtype(dtype).itemsize
    budget = cvmm_mod.VMEM_BUDGET
    nw = 2 if glu else 1
    w1i = autotune.fused_w1_tiles(d_pad, g_pad, b, nw, 1, budget=budget)
    w1t = autotune.fused_w1_tiles(d_pad, g_pad, b, nw, 1 + nw, budget=budget)
    t0 = autotune.fused_w1_tiles(d_pad, g_pad, b, 1, 1, budget=budget)
    w2 = autotune.decide("pick_tn", {"k_pad": g_pad, "n_pad": d_pad, "b": b},
                         budget=budget)
    dw = autotune.streamed_dw_tiles(d_pad, g_pad, b, budget=budget)
    if any(d.tiles is None for d in (w1i, w1t, t0, w2, dw)):
        return None
    return FusedTiles(
        w1_tn=w1i.tiles["tn"], w1_train_tn=w1t.tiles["tn"],
        t0_tn=t0.tiles["tn"], w2_tn=w2.tiles["tn"], dw_tb=dw.tiles["tb"],
        w1_nb=w1i.tiles["n_buffers"], w1_train_nb=w1t.tiles["n_buffers"],
        t0_nb=t0.tiles["n_buffers"], dw_nb=dw.tiles["n_buffers"],
        provenance=_merge_prov(w1i, w1t, t0, w2, dw))


def planned_call_tiles(k_dim: int, n_dim: int,
                       dtype=jnp.float32) -> Optional[PlannedTiles]:
    """Resolve the four grouped-GEMM tiles one planned unfused call launches
    (fwd, dX, and the two dW tiles), or None when any has no fitting tile."""
    k_pad, n_pad = round_up(k_dim, LANE), round_up(n_dim, LANE)
    b = jnp.dtype(dtype).itemsize
    budget = cvmm_mod.VMEM_BUDGET
    picks = [autotune.decide("pick_tn", {"k_pad": kp, "n_pad": npad, "b": b},
                             budget=budget)
             for kp, npad in ((k_pad, n_pad), (n_pad, k_pad),
                              (TM, k_pad), (TM, n_pad))]
    if any(d.tiles is None for d in picks):
        return None
    fwd, dx, dwk, dwn = picks
    return PlannedTiles(fwd_tn=fwd.tiles["tn"], dx_tn=dx.tiles["tn"],
                        dw_tk=dwk.tiles["tn"], dw_tn=dwn.tiles["tn"],
                        provenance=_merge_prov(*picks))


class SortKernelPlan(NamedTuple):
    """The sort path's execution decision for one shape class: which rung of
    the capability chain runs AND with what tiles — one resolution, consumed
    by core/dispatch._sort_path. ``rung`` is "pallas_fused", "pallas", or
    "ragged" (some tile working set cannot fit VMEM at any size, or the
    activation is not tile-local: degrade to XLA's grouped matmul)."""
    rung: str
    fused: Optional[FusedTiles]          # set iff rung == "pallas_fused"
    planned_w1: Optional[PlannedTiles]   # unfused w1/w1g calls (K=d, N=g)
    planned_w2: Optional[PlannedTiles]   # unfused w2 call (K=g, N=d)

    @property
    def provenance(self) -> str:
        if self.fused is not None:
            return self.fused.provenance
        if self.planned_w1 is not None:
            return _merge_prov(self.planned_w1, self.planned_w2)
        return "none"


def plan_sort_kernels(impl: str, d_model: int, expert_size: int,
                      activation: str, dtype=jnp.float32,
                      glu: bool = False) -> SortKernelPlan:
    """Resolve the sort path's rung and tiles in ONE place.

    Mirrors the old inline gate chain in core/dispatch._sort_path —
    ``pallas_supported`` decides pallas vs ragged, ``fused_supported`` decides
    fused vs unfused — but the same tuner queries that answer "does any tile
    fit" now also return WHICH tile, so degradation decisions and tile
    choices can never disagree."""
    if not impl.startswith("pallas"):
        return SortKernelPlan(rung="ragged", fused=None, planned_w1=None,
                              planned_w2=None)
    pw1 = planned_call_tiles(d_model, expert_size, dtype)
    pw2 = planned_call_tiles(expert_size, d_model, dtype)
    if pw1 is None or pw2 is None:
        # matches pallas_supported() is False: even tn=128 exhausts VMEM for
        # some launch — degrade to XLA's grouped matmul, don't raise at trace.
        return SortKernelPlan(rung="ragged", fused=None, planned_w1=None,
                              planned_w2=None)
    if impl.startswith("pallas_fused") and activation in FUSIBLE_ACTIVATIONS:
        ft = fused_mlp_tiles(d_model, expert_size, dtype, glu)
        if ft is not None:
            return SortKernelPlan(rung="pallas_fused", fused=ft,
                                  planned_w1=pw1, planned_w2=pw2)
    return SortKernelPlan(rung="pallas", fused=None, planned_w1=pw1,
                          planned_w2=pw2)


# ---------------------------------------------------------------------------
# Unfused pallas path with plan-threaded custom_vjp
# ---------------------------------------------------------------------------

def _slot_rows(a: jax.Array, new_pos: jax.Array, n: int) -> jax.Array:
    """a[new_pos, :n]; a slot past M_pad (a row held elsewhere) reads 0, and
    no slot reads a row past the live bound, which the kernels leave
    unwritten."""
    return jnp.take(a, new_pos, axis=0, mode="fill", fill_value=0)[:, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _cvmm_planned(x, new_pos, tile_expert, n_live, group_sizes, w, interpret,
                  tiles=None):
    return _planned_fwd(x, new_pos, tile_expert, n_live, group_sizes, w,
                        interpret, tiles)[0]


def _planned_fwd(x, new_pos, tile_expert, n_live, group_sizes, w, interpret,
                 tiles=None):
    n = w.shape[2]
    m_pad = tile_expert.shape[0] * TM
    x_pad = jnp.zeros((m_pad, round_up(x.shape[1], LANE)), x.dtype)
    x_pad = x_pad.at[new_pos].set(_pad_lane(x, 1))
    out_pad = cvmm_pallas(x_pad, tile_expert, _pad_w(w), interpret=interpret,
                          tn=None if tiles is None else tiles.fwd_tn,
                          n_live=n_live)
    # Residuals carry the plan arrays AND the padded activations: backward does
    # zero layout recompute and pads only the incoming cotangent.
    return _slot_rows(out_pad, new_pos, n), (x_pad, new_pos, tile_expert,
                                             n_live, group_sizes, w)


def _planned_bwd(interpret, tiles, res, g):
    x_pad, new_pos, tile_expert, n_live, group_sizes, w = res
    e, k, n = w.shape
    m_pad = x_pad.shape[0]
    g_pad = jnp.zeros((m_pad, round_up(n, LANE)), g.dtype)
    g_pad = g_pad.at[new_pos].set(_pad_lane(g, 1))
    w_pad = _pad_w(w)
    dx_pad = cvmm_pallas(g_pad, tile_expert, jnp.swapaxes(w_pad, 1, 2),
                         interpret=interpret,
                         tn=None if tiles is None else tiles.dx_tn,
                         n_live=n_live)
    dx = _slot_rows(dx_pad, new_pos, k).astype(x_pad.dtype)
    dw = cvmm_dw_pallas(x_pad, tile_expert, g_pad, e, interpret=interpret,
                        tk=None if tiles is None else tiles.dw_tk,
                        tn=None if tiles is None else tiles.dw_tn,
                        n_live=n_live)
    dw = _mask_empty(dw, group_sizes)[:, :k, :n].astype(w.dtype)
    return (dx, _float0(new_pos), _float0(tile_expert), _float0(n_live),
            _float0(group_sizes), dw)


_cvmm_planned.defvjp(_planned_fwd, _planned_bwd)


def cvmm_planned(x: jax.Array, plan: CvmmPlan, w: jax.Array,
                 *, interpret: bool,
                 tiles: Optional[PlannedTiles] = None) -> jax.Array:
    """Grouped matmul on *sorted* rows reusing a precomputed plan (no layout
    derivation inside — three calls in an MoE layer share one plan). ``tiles``
    threads a pre-resolved tile decision into every launch of this call;
    omitted -> the kernels fall back to per-launch heuristic queries."""
    return _cvmm_planned(x, plan.new_pos, plan.tile_expert, plan.n_live,
                         plan.group_sizes, w.astype(x.dtype), interpret, tiles)


# ---------------------------------------------------------------------------
# Fused MoE-MLP pipeline (gather -> grouped GEMM -> epilogue)
# ---------------------------------------------------------------------------

def fused_supported(n_tokens: int, d_model: int, expert_size: int,
                    activation: str, dtype=jnp.float32,
                    glu: bool = False) -> bool:
    """Gate for the fused pipeline: TILE-level residency only.

    The streamed kernels keep the unsorted arrays in HBM and double-buffer
    (TM, K) row tiles through VMEM, so the token count never appears in the
    residency check (``n_tokens`` is kept in the signature for
    callers/telemetry but cannot flip the answer). Callers fall back to the
    unfused path only when the activation is not tile-local or some per-step
    tile working set cannot fit at any tile size (huge d_model /
    expert_size). Sized for the worst case (training): the save_preact w1
    launch, the w2 / dX grouped GEMMs, and the streamed dW kernels — every
    kernel the fused forward AND backward will compile."""
    del n_tokens  # streamed: any row count is supported
    if activation not in FUSIBLE_ACTIVATIONS:
        return False
    return fused_mlp_tiles(d_model, expert_size, dtype, glu) is not None


def pallas_supported(d_model: int, expert_size: int, dtype=jnp.float32) -> bool:
    """Gate for the UNFUSED pallas path's tile working sets.

    ``_pick_tn`` no longer silently under-tiles: it returns None when even
    tn=128 exceeds the VMEM budget, and the kernels raise. Every grouped GEMM
    the unfused path launches (w1/w2 forward, dX, and the dW outer products)
    must therefore find a fitting tile; when this returns False, dispatchers
    should fall back to the XLA-native "ragged" impl instead of compiling a
    kernel that raises at trace time (huge d_model / expert_size configs).
    Same resolution as ``planned_call_tiles`` — the capability answer and the
    tile choice are one query."""
    return planned_call_tiles(d_model, expert_size, dtype) is not None


def _fused_fwd_impl(static, xf, plan, w1, w1g, w2, save_preact=False):
    act_name, interpret, tiles = static
    n, d = xf.shape
    # Lane-pad the feature dim only: the streamed kernel gathers rows straight
    # out of HBM, so no row-count padding is needed (sentinel row_src == n).
    xe = _pad_lane(xf, 1)
    w1_tn = w1_nb = w2_tn = None
    if tiles is not None:
        w1_tn = tiles.w1_train_tn if save_preact else tiles.w1_tn
        w1_nb = tiles.w1_train_nb if save_preact else tiles.w1_nb
        w2_tn = tiles.w2_tn
    w1_out = cvmm_fused_w1_pallas(
        xe, plan.row_src, plan.run_start, plan.run_off, plan.tile_expert,
        _pad_w(w1), _pad_w(w1g) if w1g is not None else None,
        act_name=act_name, save_preact=save_preact, interpret=interpret,
        tn=w1_tn, n_buffers=w1_nb, n_live=plan.n_live)
    u_pad = w1_out[0] if save_preact else w1_out
    y_pad = cvmm_fused_w2_pallas(u_pad, plan.tile_expert, _pad_w(w2),
                                 plan.gate_tiles, interpret=interpret,
                                 tn=w2_tn, n_live=plan.n_live)
    # row_src slack slots hold the sentinel n — out of bounds, dropped here;
    # so are the rows past the live bound, which the kernels leave unwritten.
    y = jnp.zeros((n, d), y_pad.dtype).at[plan.row_src].add(
        y_pad[:, :d], mode="drop")
    return y, xe, w1_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _moe_mlp_fused(static, xf, plan, w1, w1g, w2):
    return _fused_fwd_impl(static, xf, plan, w1, w1g, w2)[0]


def _fused_fwd(static, xf, plan, w1, w1g, w2):
    # Under differentiation the w1 kernel also emits the pre-activations in the
    # same grid pass (one extra HBM write each) so backward runs zero recompute
    # GEMMs; the inference/primal path keeps the lean single-output kernel.
    y, xe, w1_out = _fused_fwd_impl(static, xf, plan, w1, w1g, w2,
                                    save_preact=True)
    preact = w1_out[1:]                                   # (h,) or (h, hg)
    return y, (xe, plan, w1, w1g, w2, preact, xf.shape)


def _fused_bwd(static, res, dy):
    act_name, interpret, tiles = static
    xe, plan, w1, w1g, w2, preact, (n, d) = res
    t0_tn = t0_nb = dx_tn = dw_tb = dw_nb = None
    if tiles is not None:
        t0_tn, t0_nb = tiles.t0_tn, tiles.t0_nb
        dx_tn = tiles.w2_tn              # dX shares the (g_pad, d_pad) key
        dw_tb, dw_nb = tiles.dw_tb, tiles.dw_nb
    act = act_fn(act_name)
    e, _, gsz = w1.shape
    w1p, w2p = _pad_w(w1), _pad_w(w2)
    w1gp = _pad_w(w1g) if w1g is not None else None
    m_pad = plan.m_pad
    gate = plan.gate_tiles.reshape(m_pad)[:, None]        # (M_pad, 1) f32
    runs = (plan.row_src, plan.run_start, plan.run_off, plan.tile_expert)
    n_live = plan.n_live

    # Gather-free backward: the unsorted cotangent and activations stay in
    # HBM and stream through the same run-batched row-DMA plan as forward —
    # no tile-aligned (M_pad, K) copy of either is ever materialized.
    # Every (M_pad, .) array below holds unwritten rows past the live bound.
    # No kernel reads them (the clamped index maps stop at the bound), the
    # dX scatter drops them (sentinel row_src) and the gate cotangent is
    # read back only at the routed rows' slots (new_pos), all below it.
    dy_e = _pad_lane(dy, 1)
    # t0 = gather(dy) @ w2^T: the streamed fused kernel with an identity
    # epilogue (slack rows of live tiles zero-fill -> exactly zero there).
    t0 = cvmm_fused_w1_pallas(dy_e, *runs, jnp.swapaxes(w2p, 1, 2), None,
                              act_name="identity", interpret=interpret,
                              tn=t0_tn, n_buffers=t0_nb, n_live=n_live)
    if w1g is not None:
        h, hg = preact
        u, eltwise_vjp = jax.vjp(lambda a, b: act(a) * b, h, hg)
    else:
        (h,) = preact
        u, eltwise_vjp = jax.vjp(act, h)

    # d/dgate[r] = dy[r] . (u[r] @ w2[e]) == (dy[r] @ w2[e]^T) . u[r] = t0 . u
    dgate = jnp.sum(t0.astype(jnp.float32) * u.astype(jnp.float32), axis=1)
    du = (t0.astype(jnp.float32) * gate).astype(u.dtype)
    if w1g is not None:
        dh, dhg = eltwise_vjp(du)
    else:
        (dh,) = eltwise_vjp(du)

    # dW2 streams dy (g-operand) and fuses the gate multiply; dW1/dW1g stream
    # the activations (x-operand). Both pull straight from pl.ANY HBM.
    dw2 = _mask_empty(
        cvmm_dw_streamed_pallas(u, dy_e, *runs, e, stream_x=False,
                                gate_tiles=plan.gate_tiles,
                                interpret=interpret, tb=dw_tb,
                                n_buffers=dw_nb, n_live=n_live),
        plan.group_sizes)[:, :gsz, :d].astype(w2.dtype)
    dw1 = _mask_empty(
        cvmm_dw_streamed_pallas(xe, dh, *runs, e, stream_x=True,
                                interpret=interpret, tb=dw_tb,
                                n_buffers=dw_nb, n_live=n_live),
        plan.group_sizes)[:, :d, :gsz].astype(w1.dtype)
    dx_pad = cvmm_pallas(dh, plan.tile_expert, jnp.swapaxes(w1p, 1, 2),
                         interpret=interpret, tn=dx_tn, n_live=n_live)
    if w1g is not None:
        dw1g = _mask_empty(
            cvmm_dw_streamed_pallas(xe, dhg, *runs, e, stream_x=True,
                                    interpret=interpret, tb=dw_tb,
                                    n_buffers=dw_nb, n_live=n_live),
            plan.group_sizes)[:, :d, :gsz].astype(w1g.dtype)
        dx_pad = dx_pad + cvmm_pallas(dhg, plan.tile_expert,
                                      jnp.swapaxes(w1gp, 1, 2),
                                      interpret=interpret, tn=dx_tn,
                                      n_live=n_live)
    else:
        dw1g = None

    dxf = jnp.zeros((n, xe.shape[1]), dx_pad.dtype).at[plan.row_src].add(
        dx_pad, mode="drop")[:, :d].astype(xe.dtype)
    dplan = CvmmPlan(
        perm=_float0(plan.perm), group_sizes=_float0(plan.group_sizes),
        new_pos=_float0(plan.new_pos), row_src=_float0(plan.row_src),
        run_start=_float0(plan.run_start), run_len=_float0(plan.run_len),
        run_off=_float0(plan.run_off), tile_expert=_float0(plan.tile_expert),
        n_live=_float0(plan.n_live),
        gate_tiles=dgate.reshape(plan.gate_tiles.shape))
    return dxf, dplan, dw1, dw1g, dw2


_moe_mlp_fused.defvjp(_fused_fwd, _fused_bwd)


def moe_mlp_fused(xf: jax.Array, plan: CvmmPlan, w1: jax.Array, w2: jax.Array,
                  w1g: Optional[jax.Array] = None, *, activation: str = "relu",
                  interpret: Optional[bool] = None,
                  tiles: Optional[FusedTiles] = None) -> jax.Array:
    """Fused dropless expert MLP: y[t] = gate * (act(x @ w1[e]) [* x @ w1g[e]]) @ w2[e].

    xf (N, d) UNSORTED activations; the gather, activation/GLU and gate multiply
    all run inside the two kernel launches (see kernels/cvmm.py). Returns the
    per-(token, expert) outputs already scatter-added back to (N, d).

    ``tiles`` threads one pre-resolved ``FusedTiles`` decision (dispatch /
    ``fused_mlp_tiles``) through every launch of this call, forward and
    backward; omitted -> resolved here once per trace (identical answer when
    tuning is disabled)."""
    if activation not in FUSIBLE_ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is not tile-local; "
                         f"fusible: {FUSIBLE_ACTIVATIONS}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dt = xf.dtype
    if tiles is None:
        tiles = fused_mlp_tiles(w1.shape[1], w1.shape[2], dt,
                                glu=w1g is not None)
    return _moe_mlp_fused((activation, interpret, tiles), xf, plan,
                          w1.astype(dt),
                          None if w1g is None else w1g.astype(dt),
                          w2.astype(dt))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def cvmm(x: jax.Array, group_sizes: jax.Array, w: jax.Array,
         impl: Optional[str] = None) -> jax.Array:
    """Grouped matmul: rows of x (sorted by expert, sizes in group_sizes) times
    w (E, K, N). Returns (rows, N)."""
    impl = impl or default_impl()
    if impl == "ragged":
        return jax.lax.ragged_dot(x, w.astype(x.dtype),
                                  group_sizes.astype(jnp.int32))
    if impl == "ref":
        return refk.cvmm_ref(x, group_sizes, w)
    if impl in ("pallas", "pallas_interpret", "pallas_fused",
                "pallas_fused_interpret"):
        new_pos, tile_expert, n_live, _ = _tile_layout(
            group_sizes, x.shape[0], w.shape[0])
        return _cvmm_planned(x, new_pos, tile_expert, n_live,
                             group_sizes.astype(jnp.int32), w.astype(x.dtype),
                             _impl_interpret(impl),
                             planned_call_tiles(x.shape[1], w.shape[2],
                                                x.dtype))
    raise ValueError(f"unknown cvmm impl {impl}")


# ---------------------------------------------------------------------------
# Decode-shaped planned CVMM (serving: tiny-M steps on a cached skeleton)
# ---------------------------------------------------------------------------
# A continuous-batching decode step routes a handful of rows (one token per
# in-flight request, K=1-2), so rebuilding a full ``make_moe_plan`` —
# argsort, tile layout, chunk-table derivation — every token is pure
# overhead: at fixed (n_tokens, k, e, d, g) the expensive pieces of the plan
# do not depend on the routing at all. ``DecodePlan`` is that routing-free
# skeleton, built once per decode shape class and cached by the serving
# layer (serving/decode_plan.DecodePlanCache); the only per-step work is
# ``decode_slots`` — a one-hot rank giving each selection its slot inside a
# dropless per-expert capacity region — which is a few tiny XLA ops inside
# the jitted step, not a plan rebuild.

class DecodePlan(NamedTuple):
    """Routing-free layout skeleton for one decode shape class.

    The per-expert capacity is the dropless worst case ``cap =
    round_up(n_tokens*k, TM)`` (every selection could route to one expert),
    so the padded row space is ``m_pad = n_experts * cap`` and
    ``tile_expert`` is the STATIC ``repeat(arange(e), cap//TM)`` — expert
    boundaries never move with the routing, which is what lets the grouped
    GEMMs launch against a cached layout. ``gather`` is the decode-shaped
    dedup plan over TOKEN rows (row_src == arange(n_tokens)): each token's
    activation row streams HBM->VMEM once and the K-way expansion happens
    through the plan's ``sel_pos`` indirection, not K duplicate row DMAs.
    ``w1_tn``/``w2_tn`` come from the tuner's "decode_gemm" shape class —
    tile decisions costed at ONE row tile instead of a training pass.
    Execution is forward-only (inference); grads never flow through it."""
    n_tokens: int
    k: int
    n_experts: int
    cap: int                     # per-expert slot capacity (TM multiple)
    tile_expert: jax.Array       # (n_experts * cap // TM,) static layout
    gather: DedupGatherPlan      # token-row dedup gather (row_src = arange)
    gather_nb: Optional[int]     # pipeline depth for the gather kernel
    w1_tn: int                   # decode_gemm tile widths (w1: d->g, w2: g->d)
    w2_tn: int
    provenance: str

    @property
    def m_pad(self) -> int:
        return self.n_experts * self.cap


def make_decode_plan(n_tokens: int, k: int, n_experts: int, d_model: int,
                     expert_size: int,
                     dtype=jnp.float32) -> Optional[DecodePlan]:
    """Build the routing-free decode skeleton for one shape class, or None
    when some launch has no fitting tile (callers fall back to the per-call
    ``make_moe_plan`` path). Reads ``cvmm.VMEM_BUDGET`` at call time."""
    b = jnp.dtype(dtype).itemsize
    d_pad = round_up(d_model, LANE)
    g_pad = round_up(expert_size, LANE)
    budget = cvmm_mod.VMEM_BUDGET
    w1 = autotune.decode_gemm_tiles(d_pad, g_pad, b, budget=budget)
    w2 = autotune.decode_gemm_tiles(g_pad, d_pad, b, budget=budget)
    gnb = autotune.dedup_gather_tiles(d_pad, b, budget=budget)
    if w1.tiles is None or w2.tiles is None:
        return None
    cap = round_up(n_tokens * k, TM)
    tile_expert = jnp.repeat(jnp.arange(n_experts, dtype=jnp.int32),
                             cap // TM)
    tok = jnp.broadcast_to(jnp.arange(n_tokens, dtype=jnp.int32)[:, None],
                           (n_tokens, k))
    gather = make_dedup_gather_plan(tok, jnp.ones((n_tokens, k), jnp.float32),
                                    n_tokens)
    return DecodePlan(
        n_tokens=n_tokens, k=k, n_experts=n_experts, cap=cap,
        tile_expert=tile_expert, gather=gather,
        gather_nb=None if gnb.tiles is None else gnb.tiles["n_buffers"],
        w1_tn=w1.tiles["tn"], w2_tn=w2.tiles["tn"],
        provenance=_merge_prov(w1, w2))


def decode_slots(plan: DecodePlan, idx: jax.Array) -> jax.Array:
    """The per-step incremental plan update: flat selection -> padded slot.

    A cumulative one-hot rank orders each selection within its expert;
    ``slot = expert*cap + rank`` lands it in the expert's static capacity
    region. Dropless by construction (rank < n*k <= cap), injective (ranks
    are distinct per expert), and a few tiny ops at decode M — this is ALL
    the per-step work the cached skeleton leaves."""
    e_flat = idx.reshape(-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(e_flat, plan.n_experts, dtype=jnp.int32)
    rank = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    return e_flat * plan.cap + rank


def dedup_gather_rows(values: jax.Array, plan: DedupGatherPlan, *,
                      interpret: Optional[bool] = None,
                      n_buffers: Optional[int] = None) -> jax.Array:
    """Per-selection row gather through a dedup plan: rows[s] = V[idx[s]].

    The streamed pass covers the plan's compacted union once (shared rows
    one DMA) and the (M,)-index ``sel_pos`` take expands back to selection
    order — ``gathered_weighted_sum_dedup`` without the weight/scatter
    epilogue, for callers that need the rows themselves (the decode MoE
    path scatters them into expert-capacity slots instead of summing).
    Forward-only: no custom_vjp, grads do not flow through it."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if n_buffers is None:
        dec = autotune.dedup_gather_tiles(round_up(values.shape[-1], LANE),
                                          jnp.dtype(values.dtype).itemsize,
                                          budget=cvmm_mod.VMEM_BUDGET)
        n_buffers = dec.tiles["n_buffers"] if dec.tiles is not None else None
    rows = cvmm_gather_rows_pallas(_pad_lane(values, 1), plan.row_src,
                                   plan.run_start, plan.run_off,
                                   interpret=interpret, n_buffers=n_buffers)
    return jnp.take(rows, plan.sel_pos, axis=0)


def moe_mlp_decode(xf: jax.Array, idx: jax.Array, gates: jax.Array,
                   plan: DecodePlan, w1: jax.Array, w2: jax.Array,
                   w1g: Optional[jax.Array] = None, *,
                   activation: str = "relu",
                   interpret: Optional[bool] = None) -> jax.Array:
    """Decode-shaped MoE MLP on a cached skeleton: y[t] = sum_k g[t,k] *
    w2[e]^T act(w1[e]^T x[t]) without any per-step plan rebuild.

    xf (n, d) tokens, idx/gates (n, k) routing. Token rows stream once
    through the skeleton's dedup gather, scatter into the static
    expert-capacity layout, run the two grouped GEMMs at the decode-tuned
    tile widths, and combine back with the gates. Matches the sort path's
    math exactly (dropless). Forward-only — serving installs it via
    ``core.dispatch.set_decode_provider`` for inference traces only."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = xf.shape
    assert n == plan.n_tokens and idx.shape == (n, plan.k)
    d_pad = round_up(d, LANE)
    x_rows = dedup_gather_rows(xf, plan.gather, interpret=interpret,
                               n_buffers=plan.gather_nb)      # (n*k, d_pad)
    slot = decode_slots(plan, idx)
    x_pad = jnp.zeros((plan.m_pad, d_pad), xf.dtype).at[slot].set(x_rows)
    h = cvmm_pallas(x_pad, plan.tile_expert, _pad_w(w1.astype(xf.dtype)),
                    interpret=interpret, tn=plan.w1_tn)
    # Activation at the XLA level; padded weight columns are zero, so acting
    # on them is harmless (w2's padded K rows are zero either way).
    u = act_fn(activation)(h)
    if w1g is not None:
        hg = cvmm_pallas(x_pad, plan.tile_expert,
                         _pad_w(w1g.astype(xf.dtype)),
                         interpret=interpret, tn=plan.w1_tn)
        u = u * hg
    y_pad = cvmm_pallas(u.astype(xf.dtype), plan.tile_expert,
                        _pad_w(w2.astype(xf.dtype)),
                        interpret=interpret, tn=plan.w2_tn)
    g_flat = gates.reshape(-1).astype(jnp.float32)
    rows = y_pad[slot].astype(jnp.float32) * g_flat[:, None]  # (n*k, d_pad)
    y = jnp.zeros((n, d_pad), jnp.float32).at[plan.gather.tok_src].add(rows)
    return y[:, :d].astype(xf.dtype)


def assemble_decode_plan(plan: DecodePlan, idx: jax.Array,
                         gates: jax.Array) -> CvmmPlan:
    """Materialize the full ``CvmmPlan`` the skeleton + one routing imply.

    The hot path never needs this — ``moe_mlp_decode`` runs straight off the
    skeleton — but the analysis plans pass and the serve bench verify the
    decode layout against the SAME invariant oracle as every other plan
    (tile purity, slot injection, chunk-table replay), so the cached-
    skeleton shortcut can never drift from the contract silently. Slots
    follow ``decode_slots``; the chunk table is derived from the scattered
    ``row_src`` exactly as ``make_moe_plan`` would."""
    k = idx.shape[-1]
    e_flat = idx.reshape(-1).astype(jnp.int32)
    g_flat = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(plan.n_tokens, dtype=jnp.int32), k)
    perm = jnp.argsort(e_flat, stable=True)
    group_sizes = jnp.bincount(e_flat,
                               length=plan.n_experts).astype(jnp.int32)
    new_pos = decode_slots(plan, idx)[perm]
    row_src = jnp.full((plan.m_pad,), plan.n_tokens,
                       jnp.int32).at[new_pos].set(tok[perm])
    run_start, run_len, run_off = _plan_runs(row_src, plan.n_tokens)
    gate_pad = jnp.zeros((plan.m_pad,), jnp.float32).at[new_pos].set(
        g_flat[perm].astype(jnp.float32))
    # The static expert regions leave slack between live tiles, so the live
    # bound is the whole layout (the decode kernels launch with it too).
    return CvmmPlan(perm=perm, group_sizes=group_sizes, new_pos=new_pos,
                    row_src=row_src, run_start=run_start, run_len=run_len,
                    run_off=run_off, tile_expert=plan.tile_expert,
                    n_live=jnp.full((1,), plan.m_pad // TM, jnp.int32),
                    gate_tiles=gate_pad.reshape(plan.m_pad // TM, TM))
