"""Attention: GQA with chunked-flash (pure JAX online softmax), sliding-window,
Transformer-XL relative-position attention, and KV-cache decode.

The chunked path is the memory-bounded workhorse for the 32k prefill / 4k train
shapes: a lax.scan over KV chunks carrying (m, l, acc) online-softmax state, so the
(Sq, Sk) score matrix is never materialized. A Pallas flash kernel covers the TPU
hot path (kernels/flash_attention.py); this module is the composable reference that
XLA also compiles well (it is the same loop structure the kernel uses).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import AttentionConfig, ModelConfig
from .layers import apply_rope, rms_norm_simple, sinusoid_positions


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    a = cfg.attention
    d = cfg.d_model
    kq, kk, kv, ko, kr = jax.random.split(key, 5)
    std = (d ** -0.5)
    p = {
        "wq": std * jax.random.normal(kq, (d, a.q_dim), dtype),
        "wk": std * jax.random.normal(kk, (d, a.kv_dim), dtype),
        "wv": std * jax.random.normal(kv, (d, a.kv_dim), dtype),
        "wo": (a.q_dim ** -0.5) * jax.random.normal(ko, (a.q_dim, d), dtype),
    }
    if a.qk_norm:
        p["q_scale"] = jnp.ones((a.head_dim,), dtype)
        p["k_scale"] = jnp.ones((a.head_dim,), dtype)
    if a.kind == "xl_rel":
        p["w_r"] = std * jax.random.normal(kr, (d, a.q_dim), dtype)
        p["u_bias"] = jnp.zeros((a.n_heads, a.head_dim), dtype)
        p["v_bias"] = jnp.zeros((a.n_heads, a.head_dim), dtype)
    return p


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _gqa_expand(q, k, v):
    """Reshape for grouped-query attention: q (B,S,H,D) -> (B,S,KV,Grp,D)."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    return q.reshape(b, s, kvh, h // kvh, dh)


# ---------------------------------------------------------------------------
# Chunked-flash core (online softmax over KV chunks)
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, window: int = 0, scale: float,
                    q_offset: int = 0, kv_chunk: int = 2048,
                    kv_len: Optional[jax.Array] = None) -> jax.Array:
    """q (B,Sq,H,D), k/v (B,Sk,KV,D) -> (B,Sq,H,D); never materializes (Sq,Sk).

    q_offset: absolute position of q[0] relative to k[0] (for caches/memory).
    kv_len: optional (B,) valid KV lengths (decode against a partially-filled cache).
    """
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    grp = h // kvh
    qg = q.reshape(b, sq, kvh, grp, dh)
    nchunks = -(-sk // kv_chunk)
    sk_pad = nchunks * kv_chunk
    if sk_pad != sk:
        pad = [(0, 0), (0, sk_pad - sk), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    kc = k.reshape(b, nchunks, kv_chunk, kvh, dh)
    vc = v.reshape(b, nchunks, kv_chunk, kvh, dh)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, cidx = xs
        k_pos = cidx * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((sq, kv_chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        mask &= (k_pos < sk)[None, :]
        if kv_len is not None:
            s = jnp.where((k_pos[None, :] < kv_len[:, None])[:, None, None, None, :],
                          s, -jnp.inf)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, grp, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, grp, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, grp, sq, dh), jnp.float32)
    # checkpoint per chunk: backward recomputes the (sq, chunk) probability block
    # instead of storing one per scan step (which would be O(Sq*Sk) memory -- the
    # exact failure mode flash attention exists to avoid).
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nchunks)))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, dh)      # (B,Sq,KV,Grp,D)->(B,Sq,H,D)
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale: float, q_pos, window: int = 0,
                     kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Single-token attention against a (possibly sequence-sharded) KV cache.

    q (B,1,H,D), k/v (B,Smax,KV,D). The (B,H,Smax) score tensor is small at decode,
    so no online softmax is needed; XLA SPMD reduces over a sharded Smax with a psum,
    which is what makes a sequence-sharded KV cache work for the long_500k shape.
    q_pos may be a scalar (lockstep decode) or (B,) per-request positions
    (continuous batching: each lane sits at its own depth).
    """
    b, _, h, dh = q.shape
    smax, kvh = k.shape[1], k.shape[2]
    grp = h // kvh
    qg = q.reshape(b, kvh, grp, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(smax)
    mask = jnp.ones((smax,), bool)
    if kv_len is not None:
        mask = pos[None, :] < kv_len[:, None]               # (B, Smax)
    if window:
        qp = jnp.asarray(q_pos, jnp.int32).reshape(-1)      # scalar or (B,)
        wmask = pos[None, :] > qp[:, None] - window         # (1 or B, Smax)
        mask = (mask if mask.ndim == 2 else mask[None, :]) & wmask
    if mask.ndim == 1:
        mask = mask[None, :]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(b, 1, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged (block) KV cache — serving's continuous-batching layout
# ---------------------------------------------------------------------------

def paged_attend(q: jax.Array, k: jax.Array, v: jax.Array, cache: Dict,
                 block_table: jax.Array, cache_index, seq_lens, *,
                 scale: float, window: int = 0,
                 kv_chunk: int = 2048) -> Tuple[jax.Array, Dict]:
    """Attention against a paged KV pool (see serving/__init__ for the full
    block-table/KV-page contract).

    cache: {"k": (P, ps, KV, D), "v": ...} — a pool of P fixed-size pages
    shared by all requests; page 0 is the reserved null/scratch page.
    block_table (B, n_blocks) maps each request's logical page j to its
    physical page id (0 = unallocated). Two modes:

    decode (Sq == 1): ``cache_index`` is the (B,) absolute write position of
    each lane's token; the new K/V scatters into (page, offset) slots and
    attention runs over the request's gathered pages with per-lane
    ``kv_len = pos + 1`` masking (scratch-page garbage beyond a lane's
    length is masked out, not read around).

    prefill chunk (Sq > 1, B == 1): ``cache_index`` is the scalar absolute
    start of this chunk and ``seq_lens`` the (1,) valid token count within
    it — padded chunk tail tokens target page id P, which is out of bounds,
    so their writes DROP; their attention rows compute garbage the caller
    discards (the engine reads logits at length-1 only).
    """
    b, sq = q.shape[0], q.shape[1]
    n_pages, ps = cache["k"].shape[0], cache["k"].shape[1]
    cdt = cache["k"].dtype
    if sq == 1:
        pos = jnp.asarray(cache_index, jnp.int32)               # (B,)
        page = jnp.take_along_axis(block_table, (pos // ps)[:, None],
                                   axis=1)[:, 0]
        off = pos % ps
        ck = cache["k"].at[page, off].set(k[:, 0].astype(cdt))
        cv = cache["v"].at[page, off].set(v[:, 0].astype(cdt))
        gk = ck[block_table].reshape(b, -1, *ck.shape[2:])
        gv = cv[block_table].reshape(b, -1, *cv.shape[2:])
        out = decode_attention(q, gk.astype(q.dtype), gv.astype(q.dtype),
                               scale=scale, q_pos=pos, window=window,
                               kv_len=pos + 1)
    else:
        if b != 1:
            raise NotImplementedError("paged prefill runs one request per "
                                      "chunk (B == 1)")
        start = jnp.asarray(cache_index, jnp.int32)             # scalar
        length = jnp.asarray(seq_lens, jnp.int32).reshape(-1)[0]
        pos = start + jnp.arange(sq)
        valid = jnp.arange(sq) < length
        lpage = jnp.minimum(pos // ps, block_table.shape[1] - 1)
        page = jnp.where(valid, block_table[0][lpage], n_pages)  # OOB: drop
        off = pos % ps
        ck = cache["k"].at[page, off].set(k[0].astype(cdt), mode="drop")
        cv = cache["v"].at[page, off].set(v[0].astype(cdt), mode="drop")
        gk = ck[block_table[0]].reshape(1, -1, *ck.shape[2:])
        gv = cv[block_table[0]].reshape(1, -1, *cv.shape[2:])
        out = flash_attention(q, gk.astype(q.dtype), gv.astype(q.dtype),
                              causal=True, window=window, scale=scale,
                              q_offset=start, kv_chunk=kv_chunk,
                              kv_len=(start + length)[None])
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Transformer-XL relative-position attention (paper's baseline architecture)
# ---------------------------------------------------------------------------

@jax.named_scope("rel_shift")
def _rel_shift(x: jax.Array) -> jax.Array:
    """(B,H,Sq,Sk) BD-term shift (Dai et al. 2019)."""
    b, h, sq, sk = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (1, 0)))
    x = x.reshape(b, h, sk + 1, sq)[:, :, 1:, :]
    return x.reshape(b, h, sq, sk)


def xl_attention(params: Dict, q: jax.Array, k: jax.Array, v: jax.Array,
                 cfg: AttentionConfig, d_model: int) -> jax.Array:
    """q (B,Sq,H,D); k/v (B,Sk,H,D) where Sk = mem + Sq. Full (small-ctx) scores."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    scale = cfg.softmax_scale or (dh ** -0.5)
    r = sinusoid_positions(sk, d_model, q.dtype)[::-1]        # distances sk-1..0
    r = (r @ params["w_r"].astype(q.dtype)).reshape(sk, h, dh)
    ac = jnp.einsum("bqhd,bkhd->bhqk", q + params["u_bias"].astype(q.dtype), k)
    bd = jnp.einsum("bqhd,khd->bhqk", q + params["v_bias"].astype(q.dtype), r)
    bd = _rel_shift(bd)
    s = (ac + bd).astype(jnp.float32) * scale
    q_pos = (sk - sq) + jnp.arange(sq)
    mask = q_pos[:, None] >= jnp.arange(sk)[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Full block-level apply
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def apply_attention(params: Dict, x: jax.Array, cfg: ModelConfig, *,
                    kind: str = "", positions: Optional[jax.Array] = None,
                    cache: Optional[Dict] = None,
                    cache_index: Optional[jax.Array] = None,
                    memory: Optional[jax.Array] = None,
                    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                    block_table: Optional[jax.Array] = None,
                    seq_lens: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Optional[Dict]]:
    """One attention sublayer (projections + core + output).

    cache: {"k": (B,Smax,KV,D), "v": ...} for decode; cache_index (B,) write pos.
    memory: XL segment memory (B, M, d_model), no grad.
    cross_kv: precomputed encoder K/V for cross-attention.
    block_table: (B, n_blocks) page table — switches the cache to the paged
    pool layout {"k": (P, ps, KV, D), ...} (see ``paged_attend``);
    ``seq_lens`` is its prefill-chunk valid-length vector.
    Returns (output, updated_cache).
    """
    a = cfg.attention
    kind = kind or a.kind
    b, s, d = x.shape
    scale = a.softmax_scale if a.softmax_scale else a.head_dim ** -0.5

    q = _split_heads(jnp.einsum("bsd,dq->bsq", x, params["wq"].astype(x.dtype)),
                     a.n_heads, a.head_dim)
    if cross_kv is not None:
        k, v = cross_kv
    else:
        src = x if memory is None else jnp.concatenate(
            [jax.lax.stop_gradient(memory.astype(x.dtype)), x], axis=1)
        k = _split_heads(jnp.einsum("bsd,dq->bsq", src, params["wk"].astype(x.dtype)),
                         a.n_kv_heads, a.head_dim)
        v = _split_heads(jnp.einsum("bsd,dq->bsq", src, params["wv"].astype(x.dtype)),
                         a.n_kv_heads, a.head_dim)

    if a.qk_norm:
        q = rms_norm_simple(q, params["q_scale"])
        k = rms_norm_simple(k, params["k_scale"])

    new_cache = None
    if kind == "xl_rel":
        out = xl_attention(params, q, k, v, a, d)
    else:
        if positions is None:
            positions = jnp.arange(s)
        if cfg.pos_encoding == "rope" and cross_kv is None:
            q = apply_rope(q, positions, a.rope_theta)
            k = apply_rope(k, positions, a.rope_theta)
        elif cfg.pos_encoding == "rope" and cross_kv is not None:
            q = apply_rope(q, positions, a.rope_theta)

        if cache is not None and cross_kv is None and block_table is not None:
            win = a.window if kind == "local" else 0
            out, new_cache = paged_attend(
                q, k, v, cache, block_table, cache_index, seq_lens,
                scale=scale, window=win, kv_chunk=a.kv_chunk)
        elif cache is not None and cross_kv is None:
            # decode: write new k/v at cache_index, attend over the filled prefix.
            idx = cache_index
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            new_cache = {"k": ck, "v": cv}
            kv_len = jnp.full((b,), idx + s, jnp.int32)
            win = a.window if kind == "local" else 0
            if s == 1:
                # decode: direct attention; causality via kv_len. Works with
                # sequence-sharded caches (SPMD psum over the seq reduction).
                out = decode_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                       scale=scale, q_pos=idx, window=win,
                                       kv_len=kv_len)
            else:
                # prefill: causal chunked-flash over the freshly written cache.
                out = flash_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                      causal=True, window=win, scale=scale,
                                      q_offset=idx, kv_chunk=a.kv_chunk,
                                      kv_len=kv_len)
        else:
            win = a.window if kind == "local" else 0
            causal = a.causal and cross_kv is None and kind != "noncausal"
            out = flash_attention(q, k, v, causal=causal,
                                  window=win, scale=scale, kv_chunk=a.kv_chunk)

    out = out.reshape(b, s, a.q_dim)
    y = jnp.einsum("bsq,qd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Dict:
    a = cfg.attention
    shape = (batch, max_len, a.n_kv_heads, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> Dict:
    """One layer's paged KV pool: P pages of ps slots each, shared by all
    requests via block tables. Page 0 is the reserved null/scratch page —
    the allocator never hands it out, so unallocated block-table entries
    (value 0) absorb writes from inactive lanes harmlessly."""
    a = cfg.attention
    shape = (n_pages, page_size, a.n_kv_heads, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
