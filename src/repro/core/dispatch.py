"""Shared selection -> planned-execution layer: every approximator lowers here.

The paper's framework (Sec. 2) reads the two-layer MLP y = W2 act(W1 x) as a
keyed memory: u = act(W1 x) scores the d_ff rows of W2, and y is the u-weighted
sum of those rows. Every approximator is then a *selection rule* (which rows,
with what weight) plus the SAME execution primitive — a weighted aggregation of
the selected rows — and this module is that primitive. MoEs select whole
expert_size-row blocks and need the grouped GEMM; PKMs and the top-K MLP select
individual rows (an expert_size-1 MoE, exactly the PEER heads of "Mixture of A
Million Experts") and need only the retrieval + weighted sum. Both ride the
CVMM plan machinery built in kernels/ops.py.

Framework -> code map (paper Sec. 2-5)
--------------------------------------
===================  =============================  ===========================
paper                selection (core/)              execution (this module)
===================  =============================  ===========================
dense / GLU          all d_ff rows, weight u        dense matmul (topk_mlp.py)
  (Eq. 1-2)
top-K act (Sec 3.1)  lax.top_k over u               weighted_value_sum over
                       (topk_mlp.py)                  the K selected W2 rows
PKM (Sec 3.2)        product-key Cartesian top-k    weighted_value_sum over
                       (pkm.py -> vidx, w)            the H*K selected values
MoE (Sec 3.3-5)      router top-k                   expert_mlp: CvmmPlan
  sigma/switch/...     (routing.py SelectionInfo)     grouped GEMM (Eq. 11)
===================  =============================  ===========================

Kernel lowering — ONE capability chain instead of one per approximator
----------------------------------------------------------------------
``expert_mlp`` (dispatch="sort", the paper-faithful dropless path)
    pallas_fused   ops.moe_mlp_fused: gather + grouped GEMM + activation/GLU
                   + gate epilogues in-kernel (streamed HBM->VMEM row DMAs)
    pallas         ops.cvmm_planned x3 on one shared CvmmPlan
    ragged         jax.lax.ragged_dot (XLA grouped matmul; CPU default)
  plus the capacity paths: "einsum" (GShard/GSPMD) and "shard_map" (explicit
  all_to_all expert parallelism) — moved verbatim from core/moe.py.

``weighted_value_sum`` (PKM aggregation, top-K sparse down-projection)
    pallas_fused,  ops.gathered_weighted_sum_dedup: the batch's selection
    pallas         union is deduplicated + value-index-sorted into ONE
                   DedupGatherPlan, the compacted block streams HBM->VMEM
                   once (co-selected rows = one DMA, adjacent indices =
                   multi-row descriptors), per-token weights apply via the
                   scatter-side indirection (both rungs lower identically;
                   the names are kept for value_sum_path reporting)
    einsum         XLA take + einsum (materializes the (N, S, d) gather —
                   the reference semantics, kept as the last rung)

Per-layer selection of the chain entry point is ``FFNConfig.impl`` ("auto"
defers to ops.default_impl(): pallas_fused on TPU, ragged elsewhere); the
capability gates (``ops.fused_supported`` / ``ops.pallas_supported`` /
``ops.gather_supported``) degrade unsupported shapes down the chain instead
of failing at trace time. ``impl="dense"`` bypasses the planned layer
entirely (full down-projection / dense 4-D value gather) as the oracle
reference for tests and ablations.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_shard_map = jax.shard_map

from ..common import act_fn, cdiv, round_up
from ..configs.base import FFNConfig
from ..sharding.context import current_mesh
from .routing import SelectionInfo


# ---------------------------------------------------------------------------
# Selection contract
# ---------------------------------------------------------------------------

class Selection(NamedTuple):
    """The framework's selection contract: which rows of a value table each
    token selected and with what weight. Built on routing.SelectionInfo for
    MoEs (idx/gates over experts); PKM retrieval and the top-K mask produce
    the same shape over values / d_ff channels."""
    idx: jax.Array       # (N, S) int row ids
    weights: jax.Array   # (N, S) aggregation weights
    n_items: int         # static number of selectable rows (E / n_values / d_ff)


def base_aux() -> Dict[str, jax.Array]:
    """The uniform aux contract: every approximator returns at least these."""
    return {"moe_reg": jnp.float32(0.0), "moe_dropped": jnp.float32(0.0)}


def selection_usage(sel: Selection) -> Dict[str, jax.Array]:
    """Usage histogram over the selected rows (experts / PKM values / top-K
    channels) for collapse analysis (paper Fig. 3/7) — scatter-based, so it
    stays cheap when n_items is large (PKM value tables)."""
    flat = sel.idx.reshape(-1)
    counts = jnp.zeros((sel.n_items,), jnp.float32).at[flat].add(1.0)
    weight = jnp.zeros((sel.n_items,), jnp.float32).at[flat].add(
        sel.weights.reshape(-1).astype(jnp.float32))
    frac = counts / (jnp.sum(counts) + 1e-9)
    ent = -jnp.sum(frac * jnp.log(frac + 1e-9))
    return {"counts": counts, "weight": weight, "usage_entropy": ent}


def resolve_impl(cfg: FFNConfig) -> str:
    """Per-layer impl knob: cfg.impl, with "auto" deferring to the global
    backend default (ops.default_impl / set_default_impl)."""
    from ..kernels import ops as kops
    return kops.default_impl() if cfg.impl == "auto" else cfg.impl


# ---------------------------------------------------------------------------
# Weighted value aggregation (PKM values / top-K W2 rows)
# ---------------------------------------------------------------------------

def dense_value_gather(values: jax.Array, idx: jax.Array) -> jax.Array:
    """The XLA-level dense value gather — materializes (N, S, d). Reference
    semantics of the einsum rung ONLY; the planned rungs must never call this
    (tripwire-tested in tests/test_core_dispatch.py)."""
    return values[idx]


def value_sum_path(cfg: FFNConfig, d_model: int, dtype=jnp.float32) -> str:
    """Which rung of the weighted-sum chain this config lowers to at this
    feature dim/dtype. The single source of the rung decision:
    ``weighted_value_sum`` executes whatever this answers (benchmarks call it
    directly for reporting)."""
    from ..kernels import ops as kops
    impl = resolve_impl(cfg)
    if impl == "dense":
        return "dense"
    if impl.startswith("pallas") and kops.gather_supported(d_model, dtype):
        return "pallas_fused" if impl.startswith("pallas_fused") else "pallas"
    return "einsum"


def weighted_value_sum(values: jax.Array, sel: Selection, n_tokens: int,
                       cfg: FFNConfig) -> jax.Array:
    """y[t] = sum_s sel.weights[t, s] * values[sel.idx[t, s]]  (N, d).

    The shared aggregation primitive: capability chain pallas_fused ->
    pallas -> einsum (see module docstring), resolved by ``value_sum_path``.
    The planned rungs build ONE DedupGatherPlan per call — the deduplicated,
    value-index-sorted union of the batch's selections — and stream the
    compacted row block HBM->VMEM once through the run-batched row-DMA
    pipeline (co-selected rows are one DMA, adjacent value indices pack into
    multi-row descriptors); per-token weights apply through the plan's
    scatter-side indirection. No (N, S, d) gather is materialized. ("dense"
    is handled by the approximators' own oracle references before calling
    here; it degrades to the einsum rung, which computes the identical
    quantity.)"""
    from ..kernels import ops as kops
    path = value_sum_path(cfg, values.shape[-1], values.dtype)
    if path in ("pallas_fused", "pallas"):
        plan = kops.make_dedup_gather_plan(sel.idx, sel.weights,
                                           values.shape[0])
        return kops.gathered_weighted_sum_dedup(
            values, plan, n_tokens,
            interpret=True if resolve_impl(cfg).endswith("_interpret")
            else None)
    rows = dense_value_gather(values, sel.idx)
    return jnp.einsum("ns,nsd->nd", sel.weights.astype(rows.dtype), rows)


# ---------------------------------------------------------------------------
# Expert MLP execution (MoE family) — moved from core/moe.py
# ---------------------------------------------------------------------------

def _expert_ffn(cfg: FFNConfig, h_pre, h_gate):
    act = act_fn(cfg.activation)
    u = act(h_pre)
    if cfg.glu_experts:
        u = u * h_gate
    return u


def _sort_path(params: Dict, xf: jax.Array, cfg: FFNConfig,
               info: SelectionInfo, e: int) -> jax.Array:
    """Dropless grouped matmul: the TPU CVMM path (paper Eq. 11).

    All pallas variants build ONE ``CvmmPlan`` per call (the layout metadata
    is shared by every kernel launch, forward and backward — kernels/ops.py).

    "pallas_fused": the gather, the w1 activation/GLU epilogue and the w2 gate
    multiply run inside the grouped-GEMM kernels; nothing between the routing
    and the final scatter-add is materialized at the XLA level. The gather
    streams rows HBM->VMEM through a double-buffered DMA pipeline, so
    ``fused_supported`` gates only on tile-level residency (activation
    fusibility + per-step tile working set) — production token counts no
    longer fall back to the unfused path.

    "pallas"/"ragged"/"ref": 1. flatten (token, k) pairs; 2. stable-argsort by
    expert id (the paper's CUDA kernel does exactly this reordering); 3.
    grouped matmul where row-groups share an expert matrix; 4. scatter-add
    results back per token, weighted by the gates.

    A layer that holds a share of the experts (``cfg.holds_share``) gets
    ``info`` with local expert ids and the id ``e`` on rows routed to experts
    held elsewhere (``moe.held_share``): those rows join no group and get no
    slot, so no kernel gathers or scatters them. The plan's static row bound
    stays N*K, so every row a token could route here fits (nothing drops);
    the tiles past the held rows are slack, zero rows through the grid.

    Under an active mesh the whole pipeline runs REPLICATED inside a
    ``shard_map`` (every device computes the full layer on gathered inputs):
    GSPMD cannot partition the Mosaic kernels at all. The sort path is the
    single-shard rung of the capability chain; "einsum" (GSPMD) and
    "shard_map" (explicit EP) are the sharded dispatches.
    """
    mesh = current_mesh()
    if mesh is None:
        return _sort_local(params, xf, cfg, info, e)
    names = [name for name in ("we1", "we1g", "we2") if name in params]

    def local(xf, idx, gates, *weights):
        sel = SelectionInfo(probs=None, sel=None, idx=idx, gates=gates)
        return _sort_local(dict(zip(names, weights)), xf, cfg, sel, e)

    # check_vma=False: the kernels' outputs carry no varying-axes type. The
    # transpose then scales replicated cotangents by 1/devices and psums
    # them back, so gradients stay exact.
    return _shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)(
        xf, info.idx, info.gates, *(params[name] for name in names))


def _sort_local(params: Dict, xf: jax.Array, cfg: FFNConfig,
                info: SelectionInfo, e: int) -> jax.Array:
    """``_sort_path`` on one device's (full) arrays."""
    from ..kernels import ops as kops  # local import: kernels optional at import

    n, d = xf.shape
    k = cfg.k
    impl = resolve_impl(cfg)
    if impl in ("einsum", "dense"):
        # value-sum-chain names have no meaning for the grouped GEMM: the
        # XLA-native rung of the sort path is the ragged grouped matmul.
        impl = "ragged"

    if impl.startswith("pallas"):
        # One resolution for the whole call: the rung of the capability chain
        # AND the tile choices come from the same tuner queries
        # (kernels/autotune.py), so "no tile fits" degradation and "which
        # tile" can never disagree. rung == "ragged" covers the old
        # pallas_supported() fallback: even the unfused kernels cannot tile
        # this d_model/expert_size into VMEM — use XLA's grouped matmul
        # instead of failing at trace time.
        kplan = kops.plan_sort_kernels(impl, d, cfg.expert_size,
                                       cfg.activation, xf.dtype,
                                       glu=cfg.glu_experts)
        if kplan.rung == "ragged":
            impl = "ragged"

    if impl.startswith("pallas"):
        w1 = params["we1"].astype(xf.dtype)
        w2 = params["we2"].astype(xf.dtype)
        w1g = params["we1g"].astype(xf.dtype) if cfg.glu_experts else None
        plan = kops.make_moe_plan(info.idx, info.gates, n, e,
                                  drop_unheld=cfg.holds_share)
        if kplan.rung == "pallas_fused":
            return kops.moe_mlp_fused(
                xf, plan, w1, w2, w1g, activation=cfg.activation,
                interpret=True if impl.endswith("_interpret") else None,
                tiles=kplan.fused)
        # unfused pallas: gather/sort at the XLA level, plan reused by all
        # three grouped GEMMs (and their backward) — no layout recompute.
        interpret = kops._impl_interpret(impl)
        src = jnp.repeat(jnp.arange(n), k)[plan.perm]     # sorted rows' tokens
        x_sorted = xf[src]                                # (N*K, d) gathered rows
        h = kops.cvmm_planned(x_sorted, plan, w1, interpret=interpret,
                              tiles=kplan.planned_w1)
        hg = (kops.cvmm_planned(x_sorted, plan, w1g, interpret=interpret,
                                tiles=kplan.planned_w1)
              if cfg.glu_experts else None)
        u = _expert_ffn(cfg, h, hg)
        y_sorted = kops.cvmm_planned(u, plan, w2, interpret=interpret,
                                     tiles=kplan.planned_w2)
        g_flat = info.gates.reshape(-1)
        y_sorted = y_sorted * g_flat[plan.perm][:, None].astype(y_sorted.dtype)
        out = jnp.zeros_like(xf)
        return out.at[src].add(y_sorted)

    e_flat = info.idx.reshape(-1)                         # (N*K,)
    g_flat = info.gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(n), k)

    perm = jnp.argsort(e_flat, stable=True)               # CVMM preprocessing sort
    e_sorted = e_flat[perm]
    x_sorted = xf[tok[perm]]                              # (N*K, d) gathered rows
    group_sizes = jnp.bincount(e_sorted, length=e)        # (E,)

    h = kops.cvmm(x_sorted, group_sizes, params["we1"].astype(xf.dtype),
                  impl=impl)
    if cfg.glu_experts:
        hg = kops.cvmm(x_sorted, group_sizes, params["we1g"].astype(xf.dtype),
                       impl=impl)
    else:
        hg = None
    u = _expert_ffn(cfg, h, hg)
    y_sorted = kops.cvmm(u, group_sizes, params["we2"].astype(xf.dtype),
                         impl=impl)
    y_sorted = y_sorted * g_flat[perm][:, None].astype(y_sorted.dtype)

    out = jnp.zeros_like(xf)
    out = out.at[tok[perm]].add(y_sorted)
    return out


# --- capacity (GShard) dispatch: einsum under pjit, shard_map explicit EP ---

def _capacity(n_tokens: int, k: int, e: int, factor: float, multiple: int = 8) -> int:
    return max(multiple, round_up(int(cdiv(n_tokens * k, e) * factor), multiple))


def _pack_capacity(xf, info: SelectionInfo, e: int, cap: int):
    """Scatter tokens into an (E, C, d) buffer. Returns buffer + combine metadata."""
    n, d = xf.shape
    k = info.idx.shape[-1]
    e_flat = info.idx.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)       # (NK, E)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1   # rank in expert
    keep = pos < cap
    tok = jnp.repeat(jnp.arange(n), k)
    e_safe = jnp.where(keep, e_flat, 0)
    p_safe = jnp.where(keep, pos, 0)
    buf = jnp.zeros((e, cap, d), xf.dtype)
    buf = buf.at[e_safe, p_safe].add(xf[tok] * keep[:, None].astype(xf.dtype),
                                     mode="drop")
    return buf, (tok, e_safe, p_safe, keep)


def _combine_capacity(buf_out, info: SelectionInfo, meta, n: int) -> jax.Array:
    tok, e_safe, p_safe, keep = meta
    g_flat = info.gates.reshape(-1)
    rows = buf_out[e_safe, p_safe]                            # (NK, d)
    rows = rows * (g_flat * keep.astype(g_flat.dtype))[:, None].astype(rows.dtype)
    out = jnp.zeros((n, buf_out.shape[-1]), buf_out.dtype)
    return out.at[tok].add(rows, mode="drop")


def _einsum_path(params: Dict, xf: jax.Array, cfg: FFNConfig,
                 info: SelectionInfo, e: int) -> Tuple[jax.Array, jax.Array]:
    n, d = xf.shape
    cap = _capacity(n, cfg.k, e, cfg.capacity_factor)
    buf, meta = _pack_capacity(xf, info, e, cap)
    # Constrain the buffer to expert-sharding so GSPMD materializes the dispatch
    # collective here rather than all-gathering the expert weights.
    if current_mesh() is not None:
        buf = jax.lax.with_sharding_constraint(
            buf, jax.sharding.NamedSharding(current_mesh(), P("model", None, None)))
    h = jnp.einsum("ecd,edg->ecg", buf, params["we1"].astype(xf.dtype))
    hg = (jnp.einsum("ecd,edg->ecg", buf, params["we1g"].astype(xf.dtype))
          if cfg.glu_experts else None)
    u = _expert_ffn(cfg, h, hg)
    buf_out = jnp.einsum("ecg,egd->ecd", u, params["we2"].astype(xf.dtype))
    if current_mesh() is not None:
        buf_out = jax.lax.with_sharding_constraint(
            buf_out, jax.sharding.NamedSharding(current_mesh(), P("model", None, None)))
    y = _combine_capacity(buf_out, info, meta, n)
    dropped = 1.0 - jnp.mean(meta[3].astype(jnp.float32))
    return y, dropped


def ep_local_plan(e_local: int, cap_g: int, n_experts_hint: int = 0):
    """The expert-sharded CvmmPlan one EP shard executes: after the dispatch
    all_to_all, a shard holds a DENSE (E/mp, C*mp, d) capacity buffer — every
    row's expert is known statically (row r belongs to expert r // cap_g), so
    the plan is input-independent and built once per (E/mp, C*mp) shape from
    concrete arrays (it closes over the shard_map body as constants). Riding
    ``make_moe_plan`` keeps EP on the same layout/chunk-table machinery as the
    dropless sort path, so ``ops.plan_dma_stats`` telemetry (descriptor
    counts, chunk_hist) stays meaningful under expert parallelism — and the
    plan-invariant pass (repro.analysis.plans) verifies the EP shard plans
    through this entry point, not a re-derivation."""
    from ..kernels import ops as kops
    n_rows = e_local * cap_g
    idx = jnp.repeat(jnp.arange(e_local, dtype=jnp.int32), cap_g)[:, None]
    gates = jnp.ones((n_rows, 1), jnp.float32)
    return kops.make_moe_plan(idx, gates, n_rows, e_local)


_ep_local_plan = ep_local_plan        # shard_map bodies predate the public name


def ep_plan_stats(cfg: FFNConfig, n_tokens: int, e: int, mesh) -> Dict:
    """Telemetry: DMA-descriptor stats of the CvmmPlan an EP shard runs for a
    given (token count, expert count, mesh). The EP buffer is fully
    contiguous, so the plan packs whole tiles into single descriptors —
    benchmarks/tests assert the batching factor survives under EP."""
    from ..kernels import ops as kops
    mp = mesh.shape["model"]
    n_shards = 1
    for a in mesh.axis_names:
        n_shards *= mesh.shape[a]
    cap = _capacity(n_tokens // n_shards, cfg.k, e, cfg.capacity_factor)
    e_local, cap_g = e // mp, cap * mp
    plan = ep_local_plan(e_local, cap_g)
    stats = kops.plan_dma_stats(plan, e_local * cap_g, verify=True)
    stats.update(e_local=e_local, capacity=cap, rows_per_shard=e_local * cap_g)
    return stats


def _ep_local_ffn(cfg: FFNConfig, buf: jax.Array, w1, w2, w1g):
    """One EP shard's expert FFN on its (E_local, C_g, d) dispatch buffer,
    lowered through the shared execution machinery: the planned/grouped CVMM
    (``ops.cvmm`` — pallas kernels or XLA ragged_dot, same capability chain as
    the sort path) instead of a bespoke einsum. ``impl="einsum"/"dense"``
    keeps the einsum as the reference rung."""
    from ..kernels import ops as kops
    impl = resolve_impl(cfg)
    e_local, cap_g, d = buf.shape
    if impl in ("einsum", "dense"):
        h = jnp.einsum("ecd,edg->ecg", buf, w1)
        hg = jnp.einsum("ecd,edg->ecg", buf, w1g) if w1g is not None else None
        u = _expert_ffn(cfg, h, hg)
        return jnp.einsum("ecg,egd->ecd", u, w2)
    rows = buf.reshape(e_local * cap_g, d)                 # expert-major: sorted
    group_sizes = jnp.full((e_local,), cap_g, jnp.int32)
    cvmm_impl = impl if impl.startswith("pallas") else "ragged"
    h = kops.cvmm(rows, group_sizes, w1, impl=cvmm_impl)
    hg = (kops.cvmm(rows, group_sizes, w1g, impl=cvmm_impl)
          if w1g is not None else None)
    u = _expert_ffn(cfg, h, hg)
    out = kops.cvmm(u, group_sizes, w2, impl=cvmm_impl)
    return out.reshape(e_local, cap_g, d)


def _shard_map_path(params: Dict, xf: jax.Array, cfg: FFNConfig,
                    info: SelectionInfo, e: int) -> Tuple[jax.Array, jax.Array]:
    """Explicit EP (GShard pattern), two-tier under a multi-host mesh: tokens
    sharded over EVERY mesh axis; expert weights sharded over 'model' — the
    intra-pod ICI axis — and REPLICATED over the DCN 'pod' axis (each pod
    holds a full expert copy; the pod tier carries data parallelism, and its
    gradient all-reduce is where optim/compress.py error-feedback compression
    engages — wired in runtime/steps.py).

    Per device: pack its token block into an (E, C, d) capacity buffer, one
    all_to_all along 'model' (split experts, concat capacity) -> (E/mp, C*mp, d),
    local FFN with the resident expert shard (through the planned CVMM
    machinery — ``_ep_local_ffn``), inverse all_to_all, local combine.
    Exactly 2 all_to_alls per MoE layer, both intra-pod — the
    collective-minimal dispatch that the einsum/GSPMD path only approximates
    (see EXPERIMENTS.md SPerf). Capacity overflow accounting (the dropped
    fraction) is pmean'd over the whole mesh so telemetry matches the global
    drop rate.
    """
    mesh = current_mesh()
    n, d = xf.shape
    if mesh is None or "model" not in mesh.axis_names:
        return _einsum_path(params, xf, cfg, info, e)
    mp = mesh.shape["model"]
    all_axes = tuple(mesh.axis_names)
    n_shards = 1
    for a in all_axes:
        n_shards *= mesh.shape[a]
    if n % n_shards or e % mp or (n // n_shards) == 0:
        # token count or expert count not tileable (tiny decode batches):
        # fall back to the einsum path.
        return _einsum_path(params, xf, cfg, info, e)

    cap = _capacity(n // n_shards, cfg.k, e, cfg.capacity_factor)

    def local(xl, idxl, gatesl, w1, w2, w1g=None):
        # xl: (n_local, d); w1: (E/mp, d, g); w1g only present with GLU —
        # the non-GLU path neither ships nor multiplies a dummy gate weight.
        infol = SelectionInfo(probs=jnp.zeros((xl.shape[0], e), xl.dtype),
                              sel=jnp.zeros((xl.shape[0], e), xl.dtype),
                              idx=idxl, gates=gatesl)
        buf, meta = _pack_capacity(xl, infol, e, cap)          # (E, C, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)                   # (E/mp, C*mp, d)
        out = _ep_local_ffn(cfg, buf, w1, w2, w1g)             # (E/mp, C*mp, d)
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)                   # (E, C, d)
        y = _combine_capacity(out, infol, meta, xl.shape[0])
        dropped = 1.0 - jnp.mean(meta[3].astype(jnp.float32))
        return y, jax.lax.pmean(dropped, all_axes)

    tok_spec = P(all_axes, None)
    w_spec = P("model", None, None)
    weights = (params["we1"].astype(xf.dtype), params["we2"].astype(xf.dtype))
    if cfg.glu_experts:
        weights += (params["we1g"].astype(xf.dtype),)
    y, dropped = _shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec,) * 3 + (w_spec,) * len(weights),
        out_specs=(tok_spec, P()),
    )(xf, info.idx, info.gates, *weights)
    return y, dropped


# Serving-layer decode fast path. The engine (repro.serving) installs a
# provider around its inference traces; when it claims a call (tiny-M
# decode/prefill-chunk shapes, "sort" dispatch) the expert MLP executes on a
# cached routing-free DecodePlan skeleton (kernels/ops.moe_mlp_decode)
# instead of rebuilding a CvmmPlan per step. The provider returns None to
# decline (wrong shape, no fitting tile, mesh active) and the normal chain
# runs. Forward-only: providers must never be left installed around
# training traces — install/uninstall via serving.Engine (context-managed).
_DECODE_PROVIDER = None


def set_decode_provider(fn) -> None:
    """Install (or with ``None`` remove) the decode fast-path provider:
    ``fn(params, xf, cfg, info, e) -> Optional[y]``."""
    global _DECODE_PROVIDER
    _DECODE_PROVIDER = fn


def expert_mlp(params: Dict, xf: jax.Array, cfg: FFNConfig,
               info: SelectionInfo, e: int) -> Tuple[jax.Array, jax.Array]:
    """Planned execution of one MoE layer's expert MLP at a fixed selection.

    Returns (y (N, d), dropped fraction). cfg.dispatch picks the dispatch
    strategy ("sort" = dropless CVMM, "einsum" = GShard capacity under pjit,
    "shard_map" = explicit all_to_all EP); the kernel chain within "sort" is
    resolved here (resolve_impl + capability gates), not by the caller."""
    if cfg.dispatch == "sort":
        if _DECODE_PROVIDER is not None:
            y = _DECODE_PROVIDER(params, xf, cfg, info, e)
            if y is not None:
                return y, jnp.float32(0.0)  # dropless, same as _sort_path
        return _sort_path(params, xf, cfg, info, e), jnp.float32(0.0)
    if cfg.dispatch == "shard_map":
        return _shard_map_path(params, xf, cfg, info, e)
    return _einsum_path(params, xf, cfg, info, e)
