"""Layer stack: scan-over-layers with heterogeneous layer patterns.

Depth is executed as lax.scan over "segments". A segment is (pattern entries,
repeats): uniform models have one segment ([attn+ffn], n_layers); gemma3's 5:1
local:global is ([local x5, global], 10) + remainder; zamba2 is ([ssm x5,
shared-block], 13) + remainder. Scanning keeps the HLO O(1) in depth -- essential for
compiling 62-layer models with 512 SPMD partitions in the dry-run.

'shared_*' entries reference ONE set of weights (zamba2's shared attention+MLP
block); they are closed over, not stacked, and every application reuses them (this is
exactly the shared-layer setting the paper's Limitations section motivates MoE for).

Caches mirror the segment structure: {'segments': [ {entry_i: stacked (repeats, ...)
arrays} ]}. The same scan drives train, prefill and decode.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import BlockSpecEntry, ModelConfig
from ..sharding.logical import SP_RULES, with_logical_constraint
from .attention import (apply_attention, init_attention,
                        init_cache as init_attn_cache,
                        init_paged_cache as init_attn_paged_cache)
from .ffn import apply_ffn, init_ffn
from .layers import apply_norm, dropout, init_norm
from .mamba2 import apply_ssm, init_ssm, init_ssm_cache


@dataclass(frozen=True)
class Segment:
    entries: Tuple[BlockSpecEntry, ...]
    repeats: int


def plan_segments(cfg: ModelConfig, n_layers: Optional[int] = None) -> List[Segment]:
    n = n_layers if n_layers is not None else cfg.n_layers
    pattern = cfg.pattern or (BlockSpecEntry(mixer="attn", ffn="ffn"),)
    p = len(pattern)
    segs = []
    if cfg.first_dense_layers and n_layers is None:
        # leading dense layers: a segment of their own before the pattern's
        k = cfg.first_dense_layers
        segs.append(Segment((BlockSpecEntry(mixer="attn", ffn="dense_ffn"),),
                            k))
        n -= k
    if n // p:
        segs.append(Segment(tuple(pattern), n // p))
    if n % p:
        segs.append(Segment(tuple(pattern[: n % p]), 1))
    return segs


def _needs_shared(cfg: ModelConfig) -> bool:
    return any(e.mixer == "shared_attn" or e.ffn == "shared_ffn"
               for e in (cfg.pattern or ()))


# ---------------------------------------------------------------------------
# One block (pattern entry)
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, entry: BlockSpecEntry, dtype,
               ep_degree: int = 0, cross: bool = False) -> Dict:
    keys = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    if entry.mixer == "attn":
        p["norm1"] = init_norm(cfg, cfg.d_model, dtype)
        p["attn"] = init_attention(keys[0], cfg, dtype)
    elif entry.mixer == "ssm":
        p["norm1"] = init_norm(cfg, cfg.d_model, dtype)
        p["ssm"] = init_ssm(keys[0], cfg, dtype)
    if cross:
        p["norm_x"] = init_norm(cfg, cfg.d_model, dtype)
        p["cross"] = init_attention(keys[2], cfg, dtype)
    if entry.ffn in ("ffn", "dense_ffn"):
        p["norm2"] = init_norm(cfg, cfg.d_model, dtype)
        p["ffn"] = init_ffn(keys[1], cfg.d_model, _block_ffn(cfg, entry),
                            cfg.n_layers, dtype, ep_degree)
    return p


def _block_ffn(cfg: ModelConfig, entry: BlockSpecEntry):
    return cfg.dense_ffn() if entry.ffn == "dense_ffn" else cfg.ffn


def init_shared_block(key, cfg: ModelConfig, dtype) -> Dict:
    """zamba2-style shared block: attention + MLP applied at many depths."""
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "attn": init_attention(k1, cfg, dtype),
        "norm2": init_norm(cfg, cfg.d_model, dtype),
        "ffn": init_ffn(k2, cfg.d_model, cfg.ffn, cfg.n_layers, dtype),
    }


def apply_block(params: Dict, shared: Optional[Dict], x: jax.Array,
                cfg: ModelConfig, entry: BlockSpecEntry, *,
                rng: Optional[jax.Array], train: bool,
                positions: Optional[jax.Array],
                cache: Optional[Dict], cache_index,
                memory: Optional[jax.Array] = None,
                enc_out: Optional[jax.Array] = None,
                cross_cache: Optional[Dict] = None,
                block_table: Optional[jax.Array] = None,
                seq_lens: Optional[jax.Array] = None,
                sp: bool = False) -> Tuple[jax.Array, Dict, Optional[Dict], Optional[jax.Array]]:
    """Pre-norm residual block. Returns (x, aux, new_cache, new_memory).
    An MoE layer with a selection bias adds its per-expert ``load`` and the
    ``moe_rows_held``, ``moe_tiles_live`` and ``moe_tiles`` counts to aux."""
    aux = {"moe_reg": jnp.float32(0.0), "moe_dropped": jnp.float32(0.0)}
    new_cache = {}
    new_memory = None
    r1 = r2 = r3 = None
    if rng is not None:
        r1, r2, r3 = jax.random.split(rng, 3)

    # Norms and residual adds run under a "block" scope of their own, beside
    # the sublayers' "attention" and "moe" scopes (profiler op names).
    @jax.named_scope("block")
    def norm(p, h):
        return apply_norm(p, h, cfg)

    @jax.named_scope("block")
    def residual(h, r, y):
        h = h + dropout(r, y, cfg.dropout, train)
        return (with_logical_constraint(h, ("batch", "seq", None), SP_RULES)
                if sp else h)

    mixer_params = params
    mixer = entry.mixer
    if mixer == "shared_attn":
        mixer_params = shared
        mixer = "attn"

    if mixer == "attn":
        h = norm(mixer_params["norm1"], x)
        if cfg.pos_encoding == "xl_rel" and memory is not None:
            with jax.named_scope("block"):
                new_memory = jax.lax.stop_gradient(jnp.concatenate(
                    [memory.astype(x.dtype), h], axis=1)[:, -memory.shape[1]:])
        y, c = apply_attention(mixer_params["attn"], h, cfg,
                               kind=entry.attn_kind, positions=positions,
                               cache=cache.get("self") if cache else None,
                               cache_index=cache_index, memory=memory,
                               block_table=block_table, seq_lens=seq_lens)
        if c is not None:
            new_cache["self"] = c
        x = residual(x, r1, y)
    elif mixer == "ssm":
        h = norm(params["norm1"], x)
        y, c = apply_ssm(params["ssm"], h, cfg,
                         cache=cache.get("ssm") if cache else None)
        if c is not None:
            new_cache["ssm"] = c
        x = residual(x, r1, y)

    if "cross" in params and (enc_out is not None or cross_cache is not None):
        h = norm(params["norm_x"], x)
        if cross_cache is not None:
            kv = (cross_cache["k"].astype(h.dtype), cross_cache["v"].astype(h.dtype))
            y, _ = apply_attention(params["cross"], h, cfg, positions=positions,
                                   cross_kv=kv)
            new_cache["cross"] = cross_cache      # static after prefill; pass through
        else:
            y, _ = _cross_attend(params["cross"], h, enc_out, cfg, positions)
        x = residual(x, r3, y)

    ffn_kind = entry.ffn
    if ffn_kind != "none":
        fp = shared["ffn"] if ffn_kind == "shared_ffn" else params["ffn"]
        fn = shared["norm2"] if ffn_kind == "shared_ffn" else params["norm2"]
        h = norm(fn, x)
        y, faux = apply_ffn(fp, h, _block_ffn(cfg, entry), rng=r2, train=train)
        aux = {k: aux[k] + faux.get(k, 0.0) for k in aux}
        aux.update({k: faux[k] for k in ("load", "moe_rows_held",
                                         "moe_tiles_live", "moe_tiles")
                    if k in faux})
        x = residual(x, r2, y)
    return x, aux, (new_cache or None), new_memory


def _cross_attend(cparams, h, enc_out, cfg, positions):
    from .attention import _split_heads
    a = cfg.attention
    k = _split_heads(jnp.einsum("bsd,dq->bsq", enc_out,
                                cparams["wk"].astype(h.dtype)), a.n_kv_heads, a.head_dim)
    v = _split_heads(jnp.einsum("bsd,dq->bsq", enc_out,
                                cparams["wv"].astype(h.dtype)), a.n_kv_heads, a.head_dim)
    return apply_attention(cparams, h, cfg, positions=positions, cross_kv=(k, v))


def cross_kv_cache(cparams, enc_out, cfg) -> Dict:
    """Precompute encoder K/V for decode (whisper prefill)."""
    from .attention import _split_heads
    a = cfg.attention
    k = _split_heads(jnp.einsum("bsd,dq->bsq", enc_out,
                                cparams["wk"].astype(enc_out.dtype)),
                     a.n_kv_heads, a.head_dim)
    v = _split_heads(jnp.einsum("bsd,dq->bsq", enc_out,
                                cparams["wv"].astype(enc_out.dtype)),
                     a.n_kv_heads, a.head_dim)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Stack init / apply
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, dtype, *, n_layers: Optional[int] = None,
               ep_degree: int = 0, cross: bool = False) -> Dict:
    segs = plan_segments(cfg, n_layers)
    key, skey = jax.random.split(key)
    params: Dict[str, Any] = {"segments": []}
    if _needs_shared(cfg):
        params["shared"] = init_shared_block(skey, cfg, dtype)
    for seg in segs:
        seg_params = {}
        for ei, entry in enumerate(seg.entries):
            key, ekey = jax.random.split(key)
            ekeys = jax.random.split(ekey, seg.repeats)
            seg_params[f"e{ei}"] = jax.vmap(
                lambda kk: init_block(kk, cfg, entry, dtype, ep_degree, cross)
            )(ekeys)
        params["segments"].append(seg_params)
    return params


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                     *, n_layers: Optional[int] = None) -> Dict:
    segs = plan_segments(cfg, n_layers)

    def entry_cache(entry):
        c = {}
        if entry.mixer in ("attn", "shared_attn"):
            c["self"] = init_attn_cache(cfg, batch, max_len, dtype)
        elif entry.mixer == "ssm":
            c["ssm"] = init_ssm_cache(cfg, batch)
        return c

    cache = {"segments": []}
    for seg in segs:
        seg_cache = {}
        for ei, entry in enumerate(seg.entries):
            ec = entry_cache(entry)
            seg_cache[f"e{ei}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (seg.repeats,) + a.shape).copy(), ec)
        cache["segments"].append(seg_cache)
    return cache


def init_paged_stack_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                           dtype, *, n_layers: Optional[int] = None) -> Dict:
    """Paged KV pools mirroring the stack structure (page 0 reserved).

    The pool shape is batch-independent: the per-request mapping lives in
    the block table threaded through ``apply_stack`` instead.
    """
    segs = plan_segments(cfg, n_layers)

    def entry_cache(entry):
        if entry.mixer in ("attn", "shared_attn"):
            return {"self": init_attn_paged_cache(cfg, n_pages, page_size,
                                                  dtype)}
        if entry.mixer == "ssm":
            raise NotImplementedError("paged cache: ssm mixers unsupported")
        return {}

    cache = {"segments": []}
    for seg in segs:
        seg_cache = {}
        for ei, entry in enumerate(seg.entries):
            ec = entry_cache(entry)
            seg_cache[f"e{ei}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (seg.repeats,) + a.shape).copy(), ec)
        cache["segments"].append(seg_cache)
    return cache


def _add_aux(a: Dict, b: Dict) -> Dict:
    """Sum of two aux dicts, key by key; a key one of them lacks counts 0."""
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in {**a, **b}}


def apply_stack(params: Dict, x: jax.Array, cfg: ModelConfig, *,
                rng: Optional[jax.Array] = None, train: bool = False,
                positions: Optional[jax.Array] = None,
                cache: Optional[Dict] = None, cache_index=None,
                mems: Optional[jax.Array] = None,
                enc_out: Optional[jax.Array] = None,
                cross_caches: Optional[Dict] = None,
                block_table: Optional[jax.Array] = None,
                seq_lens: Optional[jax.Array] = None,
                remat: str = "none", sp: bool = False,
                n_layers: Optional[int] = None):
    """Run all segments. Returns (x, aux, new_cache, new_mems). Where MoE
    layers report a per-expert load, aux["loads"] holds them in the layout of
    the stack's parameters ({"segments": [{"e<i>": (repeats, E)}]}), and
    aux["moe_rows_held"], aux["moe_tiles_live"] and aux["moe_tiles"] their
    held rows, live row tiles and row tiles, summed."""
    segs = plan_segments(cfg, n_layers)
    shared = params.get("shared")
    aux_tot = {"moe_reg": jnp.float32(0.0), "moe_dropped": jnp.float32(0.0)}
    new_cache = {"segments": []} if cache is not None else None
    new_mems_segs = [] if mems is not None else None
    all_loads = []
    layer_offset = 0

    policy = {"none": None,
              "full": jax.checkpoint_policies.nothing_saveable,
              "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable}[remat]

    for si, seg in enumerate(segs):
        seg_params = params["segments"][si]
        seg_cache = cache["segments"][si] if cache is not None else None
        seg_mems = None
        if mems is not None:
            seg_mems = mems["segments"][si]

        def body(x, xs, seg=seg, off=layer_offset):
            ep, ridx, cxs, mxs = xs
            if cfg.cast_weights_per_layer and seg.repeats > 1:
                # times exactly 1, but a 1 that depends on the loop index,
                # so the cast that follows stays inside the loop
                one = (ridx >= 0).astype(jnp.float32)
                ep = jax.tree_util.tree_map(
                    lambda a: a * one.astype(a.dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, ep)
            aux_acc = {"moe_reg": jnp.float32(0.0), "moe_dropped": jnp.float32(0.0)}
            new_c = {}
            new_m = {}
            loads = {}
            for ei, entry in enumerate(seg.entries):
                li = off + ridx * len(seg.entries) + ei
                r = jax.random.fold_in(rng, li) if rng is not None else None
                mem_i = mxs.get(f"e{ei}") if mxs is not None else None
                xc, aux, nc, nm = apply_block(
                    ep[f"e{ei}"], shared, x, cfg, entry, rng=r, train=train,
                    positions=positions,
                    cache=cxs.get(f"e{ei}") if cxs is not None else None,
                    cache_index=cache_index, memory=mem_i,
                    enc_out=enc_out,
                    cross_cache=(cxs.get(f"e{ei}", {}) or {}).get("cross")
                    if cxs is not None else None,
                    block_table=block_table, seq_lens=seq_lens,
                    sp=sp)
                x = xc
                if "load" in aux:
                    loads[f"e{ei}"] = aux.pop("load")
                aux_acc = _add_aux(aux_acc, aux)
                if nc is not None:
                    new_c[f"e{ei}"] = nc
                if nm is not None:
                    new_m[f"e{ei}"] = nm
            return x, (aux_acc, new_c, new_m, loads)

        if policy is not None:
            body = jax.checkpoint(body, policy=policy)

        xs = (seg_params, jnp.arange(seg.repeats), seg_cache, seg_mems)
        if seg.repeats == 1:
            # single application: avoid scan overhead, index the stacked params
            ep0 = jax.tree_util.tree_map(lambda a: a[0], seg_params)
            c0 = (jax.tree_util.tree_map(lambda a: a[0], seg_cache)
                  if seg_cache is not None else None)
            m0 = (jax.tree_util.tree_map(lambda a: a[0], seg_mems)
                  if seg_mems is not None else None)
            x, (aux, nc, nm, seg_loads) = body(x, (ep0, jnp.int32(0), c0, m0))
            nc = jax.tree_util.tree_map(lambda a: a[None], nc)
            nm = jax.tree_util.tree_map(lambda a: a[None], nm)
            seg_loads = jax.tree_util.tree_map(lambda a: a[None], seg_loads)
        else:
            x, (auxs, nc, nm, seg_loads) = jax.lax.scan(body, x, xs)
            aux = jax.tree_util.tree_map(lambda a: jnp.sum(a, 0), auxs)
        aux_tot = _add_aux(aux_tot, aux)
        all_loads.append(seg_loads)
        if new_cache is not None:
            new_cache["segments"].append(nc if nc else seg_cache)
        if new_mems_segs is not None:
            new_mems_segs.append(nm)
        layer_offset += seg.repeats * len(seg.entries)

    new_mems = {"segments": new_mems_segs} if new_mems_segs is not None else None
    if any(all_loads):
        aux_tot["loads"] = {"segments": all_loads}
    return x, aux_tot, new_cache, new_mems


def init_mems(cfg: ModelConfig, batch: int, dtype) -> Dict:
    """XL segment memory, mirroring the stack structure (uniform attn only)."""
    segs = plan_segments(cfg)
    out = {"segments": []}
    for seg in segs:
        seg_m = {}
        for ei, entry in enumerate(seg.entries):
            if entry.mixer == "attn":
                seg_m[f"e{ei}"] = jnp.zeros(
                    (seg.repeats, batch, cfg.xl_memory, cfg.d_model), dtype)
        out["segments"].append(seg_m)
    return out
