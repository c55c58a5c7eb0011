"""Pallas TPU kernels for CVMM — conditional (grouped) matmul, the paper's CUDA
kernel adapted to the TPU memory hierarchy (DESIGN.md Sec. 4).

Layout contract (established by ops.py, shared by every kernel here)
--------------------------------------------------------------------
Rows are sorted by expert and each expert's row-range is padded to a multiple of
the row tile TM, so **every (TM, K) row tile belongs to exactly one expert**.
ops.py computes this layout ONCE per MoE call into a ``CvmmPlan``:

  ``new_pos``     (M,)        tile-aligned slot of sorted row i
  ``row_src``     (M_pad,)    source row in the *unsorted* activations for each
                              padded slot; slack slots hold the sentinel N (one
                              past the last row) so XLA-side scatters drop them
  ``run_start``   (M_pad,)    per-tile DMA chunk table: in-tile slot where
  ``run_len``     (M_pad,)    chunk j of tile t starts, and its length (0 =
                              unused entry); see ops._plan_runs
  ``run_off``     (M_pad/TM*9,) per-tile size-class boundaries into that
                              table (chunks are grouped largest-class first)
  ``tile_expert`` (M_pad/TM,) row-tile index -> expert id (non-decreasing)
  ``gate_tiles``  (M_pad/TM, TM) float32 gate per padded slot, 0 on slack

``tile_expert`` is scalar-prefetched; BlockSpec index_maps use it to stream the
right expert's weight block HBM->VMEM. This replaces the CUDA kernel's
shared-memory reuse of the sorted expert matrix with Mosaic-scheduled DMA of one
(K, TN) weight tile per grid step. The plan is threaded through forward AND
backward via custom_vjp residuals, so backward never re-derives the layout.

Unfused kernels (building blocks, also the backward pass of the unfused path)
  cvmm_pallas     out[t] = x[t] @ w[tile_expert[t]]        grid (m_tiles, n_tiles)
  cvmm_dw_pallas  dw[e]  = sum_{t: expert(t)=e} x[t]^T g[t] grid (k, n, m); m
                  innermost — tile_expert is non-decreasing, so output-block
                  revisits are consecutive and accumulation is legal on TPU.

Run-batched row-DMA pipeline (shared by every streamed kernel below)
  ``row_src`` alone would force one ``make_async_copy`` per row (TM
  descriptors per tile). The plan therefore carries a per-tile chunk table
  (``run_start``/``run_len``/``run_off``, built by ops._plan_runs): maximal
  contiguous ``row_src`` runs, greedily decomposed into power-of-two chunks
  because DMA copy shapes must be static. Chunks are grouped by size class
  (``run_off`` boundaries), so ``_gather_issue`` runs one dynamic-bound loop
  per static class in ``_RUN_SIZES`` and issues ONE copy per chunk — no
  per-entry size dispatch, and total loop iterations == #chunks. A fully
  contiguous tile (K=1, skewed routing) is 1 descriptor instead of 128; the
  worst case (no two sources adjacent) degrades to the old per-row count.
  Slack slots belong to no chunk and keep the zero fill before the DMAs.
  The streamed operand reaches the kernel in the ``(N, P, K/P)`` row view
  (``_row_view``): on TPU a chunk may then start at any row, which a 2-D
  (N, K) array's (8, 128) tiling would forbid.

Fused/streamed pipeline (one HBM round-trip per matmul, nothing else)
  cvmm_fused_w1_pallas   gather + GEMM + activation(/GLU) epilogue. The
      unsorted activations stay in HBM (``pl.ANY`` memory space) — the
      kernel never requires whole-array VMEM residency, so it scales to
      production token counts. The chunk table is scalar-prefetched and
      drives a double-buffered DMA pipeline: on the first N-tile of row tile
      ``i`` the kernel waits for tile ``i``'s gather (issued one tile earlier
      into one of two (TM, K) VMEM scratch buffers) and immediately starts
      tile ``i+1``'s gather into the other buffer, so the HBM reads overlap
      the MXU work of the current tile. Slack outputs are finite (zero-filled
      scratch) and killed downstream by the zero gate + scatter-drop. With
      GLU both W1 and W1g blocks are read in the same grid pass and
      u = act(x@w1) * (x@w1g) is written directly. The backward pass reuses
      this kernel with ``act_name="identity"`` for t0 = gather(dy) @ w2^T —
      the cotangent rows also stream straight out of HBM.
  cvmm_fused_w2_pallas   GEMM + per-row gate multiply in the epilogue, so
      ``y_sorted * g_flat[perm]`` is never a separate XLA pass.
  cvmm_dw_streamed_pallas  dw[e] = sum x^T g with ONE operand streamed from
      the unsorted HBM array through the same pipeline (grid (n, m), m
      innermost; the stream restarts per n-pass). Backward's dW1/dW1g stream
      the activations; dW2 streams the cotangent and fuses the ``dy * gate``
      multiply into the epilogue — no tile-aligned (M_pad, K) gather copy of
      either operand is ever materialized in HBM.
  cvmm_gather_rows_pallas  the pipeline as a bare gather: unsorted HBM rows
      -> tile-aligned (M_pad, K) layout, zeros on slack. No longer on the
      MoE training path (backward streams instead), but — with the optional
      ``weight_tiles`` epilogue (per-row multiply in VMEM) — it is the
      execution kernel of the framework's weighted value aggregation.
      The production caller is ``ops.gathered_weighted_sum_dedup``
      (``DedupGatherPlan``): ``row_src`` there is the batch's DEDUPLICATED,
      value-index-SORTED selection union — ascending row ids, sentinel
      slack at the tail — so co-selected value rows cost one DMA total and
      adjacent indices form real contiguous runs for the chunk table to
      pack into multi-row descriptors (hot PKM values: whole size-32/64
      chunks instead of 128 singles). The kernel itself is layout-agnostic:
      it just executes whatever chunk table ops._plan_runs derived, which
      is why the flat per-selection ``GatherPlan``
      (ops.gathered_weighted_sum, kept for tests/telemetry) runs through
      the same code. PKM value lookup and the top-K MLP's sparse
      down-projection lower here via dispatch.weighted_value_sum, so the
      value table never needs whole-array residency and no (N, S, d) dense
      gather is materialized at the XLA level.

VMEM working set per grid step: two (TM, K) gather buffers + the (pipelined)
weight/operand and output tiles — independent of the activation row count
(``fused_w1_tn`` / ``streamed_dw_tile`` do the accounting; ``ops.fused_supported``
gates on this tile-level residency only, forward AND backward kernels).

dX on tile-aligned operands reuses cvmm_pallas with w transposed.

Tuning
------
Tile choices come from kernels/autotune.py. Every picker below (``_pick_tn``,
``fused_w1_tn``, ``streamed_dw_tile``, ``gather_tile_fits``) is a thin query
into the tuner, threading this module's ``VMEM_BUDGET`` (itself derived from
the active ``roofline.analysis.Hardware`` model — tests monkeypatch the
module attribute to shrink every picker at once). With tuning DISABLED (the
default, and what interpret-mode CI runs) the tuner answers with the static
heuristic — the largest LANE multiple dividing the padded width whose working
set fits — at zero cost, no I/O. With tuning ENABLED (``REPRO_AUTOTUNE=1`` /
``benchmarks.run --tune``) candidates are roofline-pruned and micro-benchmarked
once per (kernel, shape-class, dtype, backend) key, and winners persist to
``~/.cache/repro/autotune/<backend>.json`` (override the directory with
``REPRO_AUTOTUNE_CACHE``). Pre-warm a new backend with::

    python -m benchmarks.run --quick --tune

Interpret-mode timings only rank candidates relative to each other on the
interpreter's cost surface — they are NOT TPU numbers; the on-disk cache is
keyed per backend precisely so a CPU-tuned cache never leaks into TPU runs.
Every kernel entry point also accepts explicit tile arguments (``tn`` / ``tb``
/ ``n_buffers``) so ops.py can resolve tiles once per plan and thread them
through forward and backward instead of re-querying per call.

Static checks
-------------
The pipeline contract above is not prose-only: ``stream_schedule_step`` is the
executable source of truth for the issue/wait schedule, and
``repro.analysis.pipeline`` replays it over concrete grids at every supported
depth, proving issue/wait pairing per slot, no overwrite of an in-flight slot,
and clean warmup/drain (including ``n_tiles < n_buffers`` and the dW kernels'
per-pass re-entry). ``python -m repro.analysis.check --all`` runs that proof
plus the plan-invariant, VMEM-budget, and sharding-table passes; CI gates on
it. When changing the schedule, the chunk-table layout, or a working-set
formula, run the checker first — it fails faster than a miscompiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import act_fn
from . import autotune
from .autotune import LANE, TM

# Per-kernel VMEM working-set budget. Derived from the active Hardware model
# (0.75 * vmem_bytes = 12 MiB on the TPU model; $REPRO_VMEM_BUDGET overrides)
# and read at CALL time by every picker below, so tests that monkeypatch this
# module attribute shrink all residency gates at once.
VMEM_BUDGET = autotune.default_vmem_budget()
N_BUFFERS = 2       # default gather scratch slots (double buffering); the
                    # tuner may thread a deeper pipeline into any streamed call

# Activations that are elementwise (tile-local) and therefore legal to apply
# inside a kernel epilogue on an (TM, TN) tile.
FUSIBLE_ACTIVATIONS = ("relu", "gelu", "silu", "identity")


def _pick_tn(k_pad: int, n_pad: int, bytes_per_el: int):
    """Largest N tile (LANE multiple dividing n_pad) whose working set fits
    VMEM, or None when even tn=128 does not fit — same contract as
    ``fused_w1_tn``: callers raise (or gate via ``ops.fused_supported``)
    instead of compiling a kernel that exhausts VMEM. Thin query into the
    tuner (kernels/autotune.py): this replaces the old fixed (512, 384, 256,
    128) ladder, whose divisibility check skipped every larger legal tile for
    widths like n_pad=640 that are multiples of 128 but of neither 384 nor
    512."""
    return autotune.pick_tn(k_pad, n_pad, bytes_per_el, budget=VMEM_BUDGET)


def _require_tn(tn, kernel: str, k_pad: int):
    if tn is None:
        raise ValueError(
            f"{kernel}: no N tile fits the VMEM budget for K_pad={k_pad}; "
            f"gate calls with ops.fused_supported or use an unfused impl")
    return tn


def fused_w1_tn(k_pad: int, g_pad: int, bytes_per_el: int,
                n_weights: int, n_out: int):
    """Largest fitting N tile for the streamed gather-fused w1 kernel, or None.

    Models the kernel's FULL per-step working set — two (TM, K) gather scratch
    buffers, plus the weight tiles and output tiles (3 with GLU + save_preact)
    at 2x for Mosaic's automatic pipeline double-buffering of blocked operands.
    The activations stream row-by-row from HBM, so — unlike the retired
    whole-x-resident kernel — the row count does not appear here at all.
    Returns None rather than silently under-tiling when nothing fits: callers
    must fall back to the unfused path instead of compiling a kernel that
    exhausts VMEM. Thin query into the tuner (the working-set formula lives in
    ``autotune.ws_fused_w1``); the full decision — including pipeline depth —
    is ``autotune.fused_w1_tiles``, which ops.py threads through the plan."""
    d = autotune.fused_w1_tiles(k_pad, g_pad, bytes_per_el, n_weights, n_out,
                                budget=VMEM_BUDGET)
    return None if d.tiles is None else d.tiles["tn"]


def streamed_dw_tile(stream_w_pad: int, block_w_pad: int, bytes_per_el: int):
    """Largest tile over the BLOCKED operand's width for the streamed dW
    kernel, or None when nothing fits.

    Working set: two (TM, W_stream) gather scratch buffers, plus the blocked
    (TM, t) operand tile and the (W_stream, t) float32 output block at 2x for
    Mosaic's pipeline double-buffering. As with ``fused_w1_tn``, the streamed
    operand's row count never appears — it lives in HBM. Thin query into the
    tuner (formula: ``autotune.ws_streamed_dw``)."""
    d = autotune.streamed_dw_tiles(stream_w_pad, block_w_pad, bytes_per_el,
                                   budget=VMEM_BUDGET)
    return None if d.tiles is None else d.tiles["tb"]


def legacy_whole_x_rows(k_pad: int, bytes_per_el: int, n_weights: int,
                        n_out: int) -> int:
    """Max activation rows the RETIRED whole-x-resident w1 kernel accepted.

    The pre-streaming kernel kept the entire (N, K) unsorted activation block
    in VMEM next to one (TM, K) gather scratch, the weight tiles and the output
    tiles (at the minimum tn=128), so its residency gate capped the row count
    at roughly (VMEM_BUDGET - tiles) / row_bytes. Kept as the reference point
    for tests and benchmarks that must demonstrate the streamed kernel working
    far beyond this boundary; reads ``VMEM_BUDGET`` at call time so tests can
    shrink the budget to sweep the boundary cheaply."""
    tiles = (TM * k_pad * bytes_per_el
             + n_weights * k_pad * 128 * bytes_per_el
             + n_out * TM * 128 * max(bytes_per_el, 4))
    return max((VMEM_BUDGET - tiles) // (k_pad * bytes_per_el), 0)


# ---------------------------------------------------------------------------
# Forward kernel (unfused building block)
# ---------------------------------------------------------------------------

def _fwd_kernel(tile_expert_ref, x_ref, w_ref, o_ref):
    # x_ref: (TM, K), w_ref: (1, K, TN), o_ref: (TM, TN)
    acc = jnp.dot(x_ref[...], w_ref[0],
                  preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def cvmm_pallas(x_pad: jax.Array, tile_expert: jax.Array, w: jax.Array,
                *, interpret: bool = False,
                tn: int | None = None) -> jax.Array:
    """x_pad (M_pad, K_pad) sorted+tile-aligned rows; tile_expert (M_pad//TM,) int32;
    w (E, K_pad, N_pad). Returns (M_pad, N_pad). ``tn`` threads a pre-resolved
    tile choice (ops.py / the tuner); omitted -> heuristic query."""
    m_pad, k_pad = x_pad.shape
    e, k_w, n_pad = w.shape
    assert k_w == k_pad and m_pad % TM == 0 and k_pad % LANE == 0 and n_pad % LANE == 0
    if tn is None:
        tn = _pick_tn(k_pad, n_pad, x_pad.dtype.itemsize)
    tn = _require_tn(tn, "cvmm_pallas", k_pad)
    grid = (m_pad // TM, n_pad // tn)

    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((TM, k_pad), lambda i, j, te: (i, 0)),
                pl.BlockSpec((1, k_pad, tn), lambda i, j, te: (te[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((TM, tn), lambda i, j, te: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_expert, x_pad, w)


# ---------------------------------------------------------------------------
# dW kernel (grouped outer-product accumulation)
# ---------------------------------------------------------------------------

def _dw_kernel(tile_expert_ref, x_ref, g_ref, o_ref):
    # grid (k_tiles, n_tiles, m_tiles); m innermost.
    m = pl.program_id(2)
    e_now = tile_expert_ref[m]
    e_prev = tile_expert_ref[jnp.maximum(m - 1, 0)]
    first = jnp.logical_or(m == 0, e_now != e_prev)
    acc = jax.lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (TK, TN)

    @pl.when(first)
    def _init():
        o_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _acc():
        o_ref[0] += acc


def cvmm_dw_pallas(x_pad: jax.Array, tile_expert: jax.Array, g_pad: jax.Array,
                   n_experts: int, *, interpret: bool = False,
                   tk: int | None = None, tn: int | None = None) -> jax.Array:
    """dW (E, K_pad, N_pad) float32 from tile-aligned x (M_pad, K_pad), g (M_pad, N_pad)."""
    m_pad, k_pad = x_pad.shape
    _, n_pad = g_pad.shape
    assert m_pad % TM == 0 and k_pad % LANE == 0 and n_pad % LANE == 0
    if tk is None:
        tk = _pick_tn(TM, k_pad, x_pad.dtype.itemsize)
    if tn is None:
        tn = _pick_tn(TM, n_pad, g_pad.dtype.itemsize)
    tk = _require_tn(tk, "cvmm_dw_pallas", TM)
    tn = _require_tn(tn, "cvmm_dw_pallas", TM)
    grid = (k_pad // tk, n_pad // tn, m_pad // TM)

    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((TM, tk), lambda k, n, m, te: (m, k)),
                pl.BlockSpec((TM, tn), lambda k, n, m, te: (m, n)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn), lambda k, n, m, te: (te[m], k, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, k_pad, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_expert, x_pad, g_pad)


# ---------------------------------------------------------------------------
# Fused forward kernels
# ---------------------------------------------------------------------------

# Static DMA chunk sizes (copy shapes cannot be dynamic): the greedy
# power-of-two decomposition of a maximal contiguous row_src run, largest
# first. A full tile is one size-TM descriptor; isolated rows are size 1.
_RUN_SIZES = tuple(1 << b for b in range(TM.bit_length() - 1, -1, -1))

# The streamed-pipeline users of stream_schedule_step, in the analyzer's terms:
# how many sequential grid passes walk the row tiles per launch. The dW kernels
# re-enter the stream at i == 0 once per outer (blocked-width) pass; the fused
# w1 kernel steps the stream only on the first N-tile of each row tile, so it
# behaves as the single-pass gather. repro.analysis.pipeline replays every
# entry here at every supported depth.
STREAMED_PIPELINES = {
    "fused_w1": dict(reentrant=False),     # grid (m, n); stream stepped at j==0
    "gather": dict(reentrant=False),       # grid (m,)
    "dw_streamed": dict(reentrant=True),   # grid (b, m), m innermost; the
                                           # stream restarts on every b pass
}


def stream_slot(t, n_buffers: int):
    """Scratch slot holding row tile ``t`` at pipeline depth ``n_buffers``.

    Pure arithmetic shared by the kernels (traced ``t``) and the static hazard
    checker in ``repro.analysis.pipeline`` (concrete ``t``)."""
    return t % n_buffers


def stream_schedule_step(i, m_tiles: int, n_buffers: int, *, issue, wait,
                         when):
    """Control skeleton of the streamed gather pipeline at row tile ``i`` —
    THE source of truth for the issue/wait schedule.

    The Pallas kernels execute it with real DMA callbacks and a traced ``i``
    (``when`` is ``pl.when``); the static hazard checker
    (``repro.analysis.pipeline``) replays it with recording callbacks over
    concrete grids and proves issue/wait pairing, no slot overwrite before its
    wait, and clean warmup/drain — including ``m_tiles < n_buffers`` — at
    every supported depth. Editing the schedule here changes the kernels AND
    what the analyzer verifies; the seeded-mutant tests rely on that.

    Schedule: warm-up at i == 0 issues tiles 0..n_buffers-2 (statically
    unrolled; guarded so a short grid never touches a missing tile's chunk
    table), every step waits for tile ``i`` (issued n_buffers-1 steps
    earlier), then prefetches tile ``i + n_buffers - 1`` into the slot that
    just freed — suppressed past the last tile so no DMA is left in flight at
    the end of a pass. Returns the slot holding tile ``i``."""
    when(i == 0, lambda: issue(0))
    for t in range(1, n_buffers - 1):
        when((i == 0) & (t < m_tiles), lambda t=t: issue(t))
    wait(i)
    when(i + n_buffers - 1 < m_tiles, lambda: issue(i + n_buffers - 1))
    return stream_slot(i, n_buffers)


def _row_pack(dtype) -> int:
    """Elements of ``dtype`` packed into one 32-bit sublane word (1 for f32,
    2 for bf16)."""
    return max(1, 4 // jnp.dtype(dtype).itemsize)


def _row_view(x: jax.Array) -> jax.Array:
    """(N, K) -> (N, P, K/P) with P = ``_row_pack``: the streamed operand's HBM
    view. A 2-D array tiles its rows in groups of 8 (16 for bf16), and Mosaic
    refuses a row DMA whose start it cannot prove aligned to that tiling. In
    this view rows are an untiled leading dim and each row is exactly one
    (P, K/P) packed sublane row, so XLA lays it out with no padding
    (T(1,128) in f32, T(2,128)(2,1) in bf16) and a DMA may start at any row."""
    n, k = x.shape
    p = _row_pack(x.dtype)
    return x.reshape(n, p, k // p)


def _stream_scratch(n_buffers: int, k_pad: int, dtype):
    """VMEM slots of the streamed pipeline, in the row view's layout."""
    p = _row_pack(dtype)
    return [pltpu.VMEM((n_buffers, TM, p, k_pad // p), dtype),
            pltpu.SemaphoreType.DMA((n_buffers,))]


def _slot_tile(xs_ref, slot):
    """The (TM, K) row tile held in pipeline slot ``slot``."""
    _, tm, p, kp = xs_ref.shape
    return xs_ref[slot].reshape(tm, p * kp)


def _gate_view(gate_tiles: jax.Array) -> jax.Array:
    """(n_tiles, TM) -> (n_tiles, 1, TM): a (1, TM) block of a 2-D array is
    illegal on TPU (a second-minor block dim must be a multiple of 8 or the
    whole dim); the 3-D view is a free bitcast and its (1, 1, TM) block is."""
    return gate_tiles.reshape(gate_tiles.shape[0], 1, gate_tiles.shape[1])


def _gate_column(gate_ref):
    """The (1, 1, TM) gate block as a (TM, 1) per-row column."""
    return gate_ref[0].reshape(TM, 1)


def _run_dmas(t, row_src_ref, run_start_ref, run_off_ref, x_hbm, xs_ref,
              sem_ref, slot, *, wait: bool):
    """Issue (or wait for) the run-batched DMA chunks of row tile ``t``.

    The plan's chunk table (ops._plan_runs) batches each maximal contiguous
    ``row_src`` run into power-of-two chunks (DMA copy shapes must be
    static): ``run_start[t*TM + j]`` is chunk j's in-tile destination slot,
    and the chunks are grouped by size class with per-tile boundaries in
    ``run_off`` — class ci's chunks occupy entries [run_off[t*9+ci],
    run_off[t*9+ci+1]). The kernel therefore runs one dynamic-bound loop per
    STATIC size class and issues ONE ``make_async_copy`` per chunk, with no
    per-entry size dispatch: total loop iterations == #chunks, versus one
    copy (and one predicate) per row before run batching. Slack slots are
    covered by no chunk and keep the zeros written by ``_gather_issue``. All
    chunks of a tile signal the slot's semaphore; the wait pass reconstructs
    identical descriptors."""
    cbase = t * (len(_RUN_SIZES) + 1)
    for ci, s in enumerate(_RUN_SIZES):
        # A chunk spans s consecutive SOURCE rows, so classes larger than the
        # HBM operand's row count can never occur — skipping them keeps every
        # traced slice shape legal against the operand.
        if s > x_hbm.shape[0]:
            continue

        def body(j, _, s=s):
            off = run_start_ref[t * TM + j]
            src = row_src_ref[t * TM + off]
            # Rows index the untiled leading dim of the row view, so neither
            # offset has to be a multiple of the sublane tiling.
            cp = pltpu.make_async_copy(x_hbm.at[pl.ds(src, s)],
                                       xs_ref.at[slot, pl.ds(off, s)],
                                       sem_ref.at[slot])
            cp.wait() if wait else cp.start()
            return 0

        jax.lax.fori_loop(run_off_ref[cbase + ci], run_off_ref[cbase + ci + 1],
                          body, 0)


def _gather_issue(t, row_src_ref, run_start_ref, run_off_ref, x_hbm, xs_ref,
                  sem_ref, n_buffers: int = N_BUFFERS):
    """Zero slot ``t % n_buffers`` and start the run-batched DMAs of tile ``t``."""
    slot = stream_slot(t, n_buffers)
    xs_ref[slot] = jnp.zeros(xs_ref.shape[1:], xs_ref.dtype)
    _run_dmas(t, row_src_ref, run_start_ref, run_off_ref, x_hbm, xs_ref,
              sem_ref, slot, wait=False)


def _gather_wait(t, row_src_ref, run_start_ref, run_off_ref, x_hbm, xs_ref,
                 sem_ref, n_buffers: int = N_BUFFERS):
    """Wait for every DMA chunk issued by ``_gather_issue`` for tile ``t``."""
    slot = stream_slot(t, n_buffers)
    _run_dmas(t, row_src_ref, run_start_ref, run_off_ref, x_hbm, xs_ref,
              sem_ref, slot, wait=True)


def _stream_tile(i, row_src_ref, run_start_ref, run_off_ref, x_hbm, xs_ref,
                 sem_ref, *, axis: int = 0, n_buffers: int = N_BUFFERS):
    """Pipelined gather step for row tile ``i`` (grid dim ``axis``, sequential
    and innermost), ``n_buffers`` scratch slots deep.

    Waits for tile ``i``'s chunks (issued ``n_buffers - 1`` tiles earlier;
    warm-up issues tiles 0..n_buffers-2 inline) and immediately starts tile
    ``i + n_buffers - 1``'s DMAs into the slot that just freed, so the HBM
    reads of upcoming tiles overlap this tile's MXU work. Returns the slot
    holding tile ``i``. With the default depth 2 this is exactly the classic
    double buffer: warm-up issues tile 0, each step prefetches tile i+1.
    Kernels whose row-tile loop is an inner grid dimension (the streamed dW
    kernels) re-enter at i == 0 once per outer pass: the warm-up re-issues its
    tiles and prefetches past the last tile are suppressed, so no DMA is left
    in flight across pass boundaries.

    The actual issue/wait ordering lives in ``stream_schedule_step`` (shared
    with the static hazard checker); this wrapper only binds the DMA
    callbacks."""
    m_tiles = pl.num_programs(axis)

    def issue(t):
        _gather_issue(t, row_src_ref, run_start_ref, run_off_ref, x_hbm,
                      xs_ref, sem_ref, n_buffers)

    def wait(t):
        _gather_wait(t, row_src_ref, run_start_ref, run_off_ref, x_hbm,
                     xs_ref, sem_ref, n_buffers)

    return stream_schedule_step(i, m_tiles, n_buffers, issue=issue, wait=wait,
                                when=lambda cond, fn: pl.when(cond)(fn))


def _fused_w1_body(row_src_ref, run_start_ref, run_off_ref, x_hbm, w1_ref,
                   w1g_ref, o_u_ref, o_h_ref, o_hg_ref, xs_ref, sem_ref,
                   *, act_name: str, n_buffers: int = N_BUFFERS):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _stream_tile(i, row_src_ref, run_start_ref, run_off_ref, x_hbm,
                     xs_ref, sem_ref, n_buffers=n_buffers)
    xt = _slot_tile(xs_ref, stream_slot(i, n_buffers))
    h = jnp.dot(xt, w1_ref[0], preferred_element_type=jnp.float32)
    u = act_fn(act_name)(h)
    if w1g_ref is not None:
        hg = jnp.dot(xt, w1g_ref[0],
                     preferred_element_type=jnp.float32)
        u = u * hg
        if o_hg_ref is not None:
            o_hg_ref[...] = hg.astype(o_hg_ref.dtype)
    if o_h_ref is not None:
        o_h_ref[...] = h.astype(o_h_ref.dtype)
    o_u_ref[...] = u.astype(o_u_ref.dtype)


def _k_w1(rs, rst, rl, te, x, w1, o_u, xs, sem, **kw):
    _fused_w1_body(rs, rst, rl, x, w1, None, o_u, None, None, xs, sem, **kw)


def _k_w1_save(rs, rst, rl, te, x, w1, o_u, o_h, xs, sem, **kw):
    _fused_w1_body(rs, rst, rl, x, w1, None, o_u, o_h, None, xs, sem, **kw)


def _k_w1_glu(rs, rst, rl, te, x, w1, w1g, o_u, xs, sem, **kw):
    _fused_w1_body(rs, rst, rl, x, w1, w1g, o_u, None, None, xs, sem, **kw)


def _k_w1_glu_save(rs, rst, rl, te, x, w1, w1g, o_u, o_h, o_hg, xs, sem, **kw):
    _fused_w1_body(rs, rst, rl, x, w1, w1g, o_u, o_h, o_hg, xs, sem, **kw)


def cvmm_fused_w1_pallas(x: jax.Array, row_src: jax.Array,
                         run_start: jax.Array, run_off: jax.Array,
                         tile_expert: jax.Array, w1: jax.Array,
                         w1g: jax.Array | None, *, act_name: str,
                         save_preact: bool = False,
                         interpret: bool = False,
                         tn: int | None = None,
                         n_buffers: int | None = None):
    """Streamed gather-fused grouped GEMM with activation(/GLU) epilogue.

    x (N_rows, K_pad) — the UNSORTED activations, left in HBM (``pl.ANY``)
    and streamed through the run-batched double-buffered async-copy pipeline
    (see ``_stream_tile``); the row count is unconstrained — no multiple-of-8
    padding, no whole-array VMEM residency. row_src (M_pad,) int32 maps padded
    slots to rows of x (sentinel >= N_rows on slack; those rows get no DMA and
    stay zero-filled); run_start (M_pad,) / run_off (M_pad//TM*9,) int32 are
    the per-tile DMA chunk table (ops._plan_runs); w1/w1g (E, K_pad, G_pad).
    Returns u
    (M_pad, G_pad) in the tile-aligned sorted layout, already activated (and
    gated when w1g given). The backward pass reuses this kernel with
    ``act_name="identity"`` to stream-gather ∘ GEMM the incoming cotangent.

    ``save_preact=True`` (training: the custom_vjp forward rule) additionally
    writes the pre-activations h (and hg with GLU) in the same grid pass, so
    the backward pass needs no recompute GEMMs; returns (u, h[, hg]).

    ``tn`` / ``n_buffers`` (the N-tile width and gather pipeline depth) are
    normally resolved once per plan by ops.py via the tuner and threaded in;
    when omitted the kernel falls back to the heuristic query itself."""
    n_rows, k_pad = x.shape
    e, k_w, g_pad = w1.shape
    m_pad = row_src.shape[0]
    assert k_w == k_pad and m_pad % TM == 0
    assert k_pad % LANE == 0 and g_pad % LANE == 0
    assert run_start.shape == (m_pad,)
    assert run_off.shape == ((m_pad // TM) * (len(_RUN_SIZES) + 1),)
    n_weights = 2 if w1g is not None else 1
    n_out = (1 + n_weights) if save_preact else 1
    if tn is None:
        tn = fused_w1_tn(k_pad, g_pad, x.dtype.itemsize, n_weights, n_out)
    if tn is None:
        raise ValueError(
            f"fused w1 tile working set exceeds VMEM budget for K_pad="
            f"{k_pad}; gate calls with ops.fused_supported")
    n_buffers = N_BUFFERS if n_buffers is None else n_buffers
    grid = (m_pad // TM, g_pad // tn)

    w_spec = pl.BlockSpec((1, k_pad, tn),
                          lambda i, j, rs, rst, rl, te: (te[i], 0, j))
    o_spec = pl.BlockSpec((TM, tn), lambda i, j, rs, rst, rl, te: (i, j))
    o_shape = jax.ShapeDtypeStruct((m_pad, g_pad), x.dtype)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), w_spec]
    operands = [row_src, run_start, run_off, tile_expert, _row_view(x), w1]
    if w1g is not None:
        in_specs.append(w_spec)
        operands.append(w1g)
        kernel = _k_w1_glu_save if save_preact else _k_w1_glu
    else:
        kernel = _k_w1_save if save_preact else _k_w1
    kernel = functools.partial(kernel, act_name=act_name, n_buffers=n_buffers)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=[o_spec] * n_out,
            scratch_shapes=_stream_scratch(n_buffers, k_pad, x.dtype),
        ),
        out_shape=[o_shape] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[0] if n_out == 1 else tuple(out)


def _gather_rows_kernel(row_src_ref, run_start_ref, run_off_ref, x_hbm, o_ref,
                        xs_ref, sem_ref, *, n_buffers: int = N_BUFFERS):
    i = pl.program_id(0)
    slot = _stream_tile(i, row_src_ref, run_start_ref, run_off_ref, x_hbm,
                        xs_ref, sem_ref, n_buffers=n_buffers)
    o_ref[...] = _slot_tile(xs_ref, slot)


def _gather_rows_weighted_kernel(row_src_ref, run_start_ref, run_off_ref,
                                 x_hbm, w_ref, o_ref, xs_ref, sem_ref,
                                 *, n_buffers: int = N_BUFFERS):
    i = pl.program_id(0)
    slot = _stream_tile(i, row_src_ref, run_start_ref, run_off_ref, x_hbm,
                        xs_ref, sem_ref, n_buffers=n_buffers)
    o_ref[...] = (_slot_tile(xs_ref, slot).astype(jnp.float32)
                  * _gate_column(w_ref)).astype(o_ref.dtype)


def gather_tile_fits(k_pad: int, bytes_per_el: int,
                     n_buffers: int = N_BUFFERS) -> bool:
    """Residency gate for the streamed gather kernel's per-step working set:
    ``n_buffers`` (TM, K) scratch buffers plus the blocked output tile at 2x
    for Mosaic's pipeline double-buffering. As everywhere in the streamed
    family, the HBM operand's row count never appears — it is not
    VMEM-resident. Thin query into the tuner (``autotune.ws_gather``)."""
    return autotune.gather_fits(k_pad, bytes_per_el, n_buffers,
                                budget=VMEM_BUDGET)


def cvmm_gather_rows_pallas(x: jax.Array, row_src: jax.Array,
                            run_start: jax.Array, run_off: jax.Array,
                            weight_tiles: jax.Array | None = None,
                            *, interpret: bool = False,
                            n_buffers: int | None = None) -> jax.Array:
    """Streamed gather: unsorted HBM rows -> tile-aligned (M_pad, K_pad) copy.

    The same run-batched double-buffered DMA pipeline as the fused w1 kernel,
    with the scratch tile written straight to the blocked output (slack slots
    zero). ``weight_tiles`` (M_pad//TM, TM) float32, if given, scales each
    gathered row in the epilogue — the fused lowering of the framework's
    weighted value aggregation (PKM values, top-K W2 rows): the per-row
    weight multiply never becomes a separate XLA pass, and slack rows stay
    exactly zero (zero-filled scratch times the plan's zero weight). No
    longer called by the fused MoE backward — dW/dX stream their operands in
    place — but the bare form remains the pipeline's direct test surface."""
    n_rows, k_pad = x.shape
    m_pad = row_src.shape[0]
    assert m_pad % TM == 0 and k_pad % LANE == 0
    n_buffers = N_BUFFERS if n_buffers is None else n_buffers
    if not gather_tile_fits(k_pad, x.dtype.itemsize, n_buffers):
        raise ValueError(
            f"streamed gather tile working set exceeds VMEM budget for "
            f"K_pad={k_pad}; gate calls with ops.gather_supported")
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [row_src, run_start, run_off, _row_view(x)]
    if weight_tiles is None:
        kernel = _gather_rows_kernel
        out_spec = pl.BlockSpec((TM, k_pad), lambda i, rs, rst, rl: (i, 0))
    else:
        assert weight_tiles.shape == (m_pad // TM, TM)
        kernel = _gather_rows_weighted_kernel
        in_specs.append(pl.BlockSpec((1, 1, TM),
                                     lambda i, rs, rst, rl: (i, 0, 0)))
        operands.append(_gate_view(weight_tiles))
        out_spec = pl.BlockSpec((TM, k_pad), lambda i, rs, rst, rl: (i, 0))
    return pl.pallas_call(
        functools.partial(kernel, n_buffers=n_buffers),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(m_pad // TM,),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=_stream_scratch(n_buffers, k_pad, x.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, k_pad), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Streamed dW kernels (backward: no tile-aligned gather ever hits HBM)
# ---------------------------------------------------------------------------

def _dw_first(te_ref, m):
    e_now = te_ref[m]
    e_prev = te_ref[jnp.maximum(m - 1, 0)]
    return jnp.logical_or(m == 0, e_now != e_prev)


def _dw_accumulate(o_ref, acc, first):
    @pl.when(first)
    def _init():
        o_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _acc():
        o_ref[0] += acc


def _dw_stream_x_kernel(rs, rst, rl, te, x_hbm, g_ref, o_ref, xs_ref, sem_ref,
                        *, n_buffers: int = N_BUFFERS):
    # grid (n_tiles, m_tiles), m innermost; the stream restarts per n pass.
    m = pl.program_id(1)
    slot = _stream_tile(m, rs, rst, rl, x_hbm, xs_ref, sem_ref, axis=1,
                        n_buffers=n_buffers)
    acc = jax.lax.dot_general(_slot_tile(xs_ref, slot), g_ref[...],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (K, tb)
    _dw_accumulate(o_ref, acc, _dw_first(te, m))


def _dw_stream_g_body(rs, rst, rl, g_hbm, x_ref, gate_ref, o_ref, gs_ref,
                      sem_ref, te, n_buffers: int = N_BUFFERS):
    m = pl.program_id(1)
    slot = _stream_tile(m, rs, rst, rl, g_hbm, gs_ref, sem_ref, axis=1,
                        n_buffers=n_buffers)
    gt = _slot_tile(gs_ref, slot)
    if gate_ref is not None:
        gt = (gt.astype(jnp.float32) * _gate_column(gate_ref)).astype(gt.dtype)
    acc = jax.lax.dot_general(x_ref[...], gt, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (tb, N)
    _dw_accumulate(o_ref, acc, _dw_first(te, m))


def _dw_stream_g_kernel(rs, rst, rl, te, g_hbm, x_ref, o_ref, gs_ref, sem_ref,
                        *, n_buffers: int = N_BUFFERS):
    _dw_stream_g_body(rs, rst, rl, g_hbm, x_ref, None, o_ref, gs_ref, sem_ref,
                      te, n_buffers)


def _dw_stream_g_gate_kernel(rs, rst, rl, te, g_hbm, x_ref, gate_ref, o_ref,
                             gs_ref, sem_ref, *, n_buffers: int = N_BUFFERS):
    _dw_stream_g_body(rs, rst, rl, g_hbm, x_ref, gate_ref, o_ref, gs_ref,
                      sem_ref, te, n_buffers)


def cvmm_dw_streamed_pallas(x: jax.Array, g: jax.Array, row_src: jax.Array,
                            run_start: jax.Array, run_off: jax.Array,
                            tile_expert: jax.Array, n_experts: int, *,
                            stream_x: bool,
                            gate_tiles: jax.Array | None = None,
                            interpret: bool = False,
                            tb: int | None = None,
                            n_buffers: int | None = None) -> jax.Array:
    """dW (E, K_pad, N_pad) float32 with ONE operand streamed from unsorted HBM.

    stream_x=True : ``x`` is the UNSORTED (N_rows, K_pad) activations, left in
        HBM (``pl.ANY``) and gathered tile-by-tile through the run-batched
        DMA pipeline; ``g`` (M_pad, N_pad) is tile-aligned and blocked
        normally. (Backward's dW1/dW1g: activations never re-materialize.)
    stream_x=False: ``g`` is the UNSORTED (N_rows, N_pad) cotangent in HBM;
        ``x`` (M_pad, K_pad) is tile-aligned. ``gate_tiles`` (M_pad//TM, TM)
        float32, if given, scales the streamed rows before the outer product —
        backward's dW2 fuses the ``dy * gate`` multiply here instead of
        materializing a gated copy. Slack slots stream as zeros either way.

    Grid (blocked_w // tb, m_tiles) with the row-tile loop innermost:
    ``tile_expert`` is non-decreasing, so output-block revisits stay
    consecutive and accumulation is legal; the gather stream restarts on each
    outer pass (the scratch only ever holds two row tiles)."""
    assert gate_tiles is None or not stream_x
    m_pad = row_src.shape[0]
    if stream_x:
        n_rows, k_pad = x.shape
        mp_g, n_pad = g.shape
        stream_w, block_w, sdtype = k_pad, n_pad, x.dtype
        assert mp_g == m_pad
    else:
        mp_x, k_pad = x.shape
        n_rows, n_pad = g.shape
        stream_w, block_w, sdtype = n_pad, k_pad, g.dtype
        assert mp_x == m_pad
    assert m_pad % TM == 0 and k_pad % LANE == 0 and n_pad % LANE == 0
    assert run_start.shape == (m_pad,)
    assert run_off.shape == ((m_pad // TM) * (len(_RUN_SIZES) + 1),)
    if tb is None:
        tb = streamed_dw_tile(stream_w, block_w, sdtype.itemsize)
    if tb is None:
        raise ValueError(
            f"streamed dW tile working set exceeds VMEM budget for "
            f"W_stream={stream_w}; gate calls with ops.fused_supported")
    n_buffers = N_BUFFERS if n_buffers is None else n_buffers
    grid = (block_w // tb, m_pad // TM)
    scratch = _stream_scratch(n_buffers, stream_w, sdtype)
    blk_spec = pl.BlockSpec((TM, tb), lambda b, m, *s: (m, b))
    if stream_x:
        in_specs = [pl.BlockSpec(memory_space=pl.ANY), blk_spec]
        operands = [row_src, run_start, run_off, tile_expert, _row_view(x), g]
        out_spec = pl.BlockSpec(
            (1, k_pad, tb), lambda b, m, rs, rst, rl, te: (te[m], 0, b))
        kernel = _dw_stream_x_kernel
    else:
        in_specs = [pl.BlockSpec(memory_space=pl.ANY), blk_spec]
        operands = [row_src, run_start, run_off, tile_expert, _row_view(g), x]
        out_spec = pl.BlockSpec(
            (1, tb, n_pad), lambda b, m, rs, rst, rl, te: (te[m], b, 0))
        if gate_tiles is not None:
            assert gate_tiles.shape == (m_pad // TM, TM)
            in_specs.append(pl.BlockSpec((1, 1, TM),
                                         lambda b, m, *s: (m, 0, 0)))
            operands.append(_gate_view(gate_tiles))
            kernel = _dw_stream_g_gate_kernel
        else:
            kernel = _dw_stream_g_kernel

    return pl.pallas_call(
        functools.partial(kernel, n_buffers=n_buffers),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, k_pad, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)


def _fused_w2_kernel(tile_expert_ref, u_ref, w2_ref, gate_ref, o_ref):
    acc = jnp.dot(u_ref[...], w2_ref[0], preferred_element_type=jnp.float32)
    o_ref[...] = (acc * _gate_column(gate_ref)).astype(o_ref.dtype)


def cvmm_fused_w2_pallas(u_pad: jax.Array, tile_expert: jax.Array,
                         w2: jax.Array, gate_tiles: jax.Array,
                         *, interpret: bool = False,
                         tn: int | None = None) -> jax.Array:
    """Grouped GEMM with the per-row gate multiply fused into the epilogue.

    u_pad (M_pad, G_pad) tile-aligned; w2 (E, G_pad, N_pad);
    gate_tiles (M_pad//TM, TM) float32. Returns (M_pad, N_pad)."""
    m_pad, g_pad = u_pad.shape
    e, g_w, n_pad = w2.shape
    assert g_w == g_pad and m_pad % TM == 0
    assert g_pad % LANE == 0 and n_pad % LANE == 0
    assert gate_tiles.shape == (m_pad // TM, TM)
    if tn is None:
        tn = _pick_tn(g_pad, n_pad, u_pad.dtype.itemsize)
    tn = _require_tn(tn, "cvmm_fused_w2_pallas", g_pad)
    grid = (m_pad // TM, n_pad // tn)

    return pl.pallas_call(
        _fused_w2_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((TM, g_pad), lambda i, j, te: (i, 0)),
                pl.BlockSpec((1, g_pad, tn), lambda i, j, te: (te[i], 0, j)),
                pl.BlockSpec((1, 1, TM), lambda i, j, te: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((TM, tn), lambda i, j, te: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), u_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_expert, u_pad, w2, _gate_view(gate_tiles))
