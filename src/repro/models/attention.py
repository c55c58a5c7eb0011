"""Attention: GQA with chunked-flash (pure JAX online softmax), sliding-window,
Transformer-XL relative-position attention, and KV-cache decode.

The chunked path is the memory-bounded workhorse for the 32k prefill / 4k train
shapes: a lax.scan over KV chunks carrying (m, l, acc) online-softmax state, so the
(Sq, Sk) score matrix is never materialized. A Pallas flash kernel covers the TPU
hot path (kernels/flash_attention.py); this module is the composable reference that
XLA also compiles well (it is the same loop structure the kernel uses).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import AttentionConfig, ModelConfig
from ..kernels.xl_rel import xl_rel_bd
from ..sharding.context import current_mesh
from ..sharding.logical import TRAIN_RULES
from .layers import apply_rope, rms_norm_simple, sinusoid_positions


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    a = cfg.attention
    d = cfg.d_model
    if a.kind == "mla":
        return init_mla(key, cfg, dtype)
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


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    """Multi-head latent attention (DeepSeek-V2 Sec. 2.1) without a query
    latent: W_q (d, H*(nope+rope)); W_kv_a (d, rank+rope) down to the latent
    and the shared RoPE key; the latent's RMSNorm; W_kv_b (rank,
    H*(nope+v)) up to per-head keys and values; W_o (H*v, d)."""
    a = cfg.attention
    d, r = cfg.d_model, a.kv_lora_rank
    nope = a.head_dim - a.rope_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": d ** -0.5 * jax.random.normal(kq, (d, a.q_dim), dtype),
        "wkv_a": d ** -0.5 * jax.random.normal(
            ka, (d, r + a.rope_head_dim), dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wkv_b": r ** -0.5 * jax.random.normal(
            kb, (r, a.n_heads * (nope + a.v_head_dim)), dtype),
        "wo": a.o_dim ** -0.5 * jax.random.normal(ko, (a.o_dim, d), dtype),
    }


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
    """q (B,Sq,H,D), k (B,Sk,KV,D), v (B,Sk,KV,Dv) -> (B,Sq,H,Dv); never
    materializes (Sq,Sk).

    q_offset: absolute position of q[0] relative to k[0] (for caches/memory).
    kv_len: optional (B,) valid KV lengths (decode against a partially-filled cache).
    """
    b, sq, h, dh = q.shape
    sk, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
    grp = h // kvh
    qg = q.reshape(b, sq, kvh, grp, dh)
    nchunks = -(-sk // kv_chunk)
    sk_pad = nchunks * kv_chunk
    if sk_pad != sk:
        pad = [(0, 0), (0, sk_pad - sk), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    kc = k.reshape(b, nchunks, kv_chunk, kvh, dh)
    vc = v.reshape(b, nchunks, kv_chunk, kvh, dv)

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
    a0 = jnp.zeros((b, kvh, grp, sq, dv), jnp.float32)
    # checkpoint per chunk: backward recomputes the (sq, chunk) probability block
    # instead of storing one per scan step (which would be O(Sq*Sk) memory -- the
    # exact failure mode flash attention exists to avoid).
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nchunks)))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, dv)      # (B,Sq,KV,Grp,D)->(B,Sq,H,D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Causal chunked attention with a recomputing backward (training)
# ---------------------------------------------------------------------------
# ``flash_attention`` is differentiated through its scan: the backward keeps
# every chunk's (m, l, acc) carry, O(Sk / chunk) copies of the (Sq, Dv)
# accumulator (1.1 GB per layer at 8k tokens, 16 heads of 128, chunks of
# 512). ``lean_attention`` saves only q, k, v, the output and the row
# log-sum-exp, and its backward recomputes each chunk's probabilities from
# them (FlashAttention-2's backward): dV += P^T dO, dP = dO V^T,
# dS = P * (dP - rowsum(dO * O)), dQ += dS K, dK = dS^T Q. Products take
# the operands' dtype with float32 accumulation.

def _lean_chunks(k, v, kv_chunk):
    b, sk, kvh, dh = k.shape
    n = -(-sk // kv_chunk)
    pad = [(0, 0), (0, n * kv_chunk - sk), (0, 0), (0, 0)]
    kc = jnp.pad(k, pad).reshape(b, n, kv_chunk, kvh, dh)
    vc = jnp.pad(v, pad).reshape(b, n, kv_chunk, kvh, v.shape[-1])
    return jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), n


def _lean_scores(qg, kb, cidx, *, sk, causal, scale):
    """One chunk's scaled scores (B,KV,G,Sq,C) in float32, -inf where
    masked (after the causal diagonal, or past the keys)."""
    sq, c = qg.shape[1], kb.shape[1]
    k_pos = cidx * c + jnp.arange(c)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    mask = (k_pos < sk)[None, :]
    if causal:
        mask = mask & (jnp.arange(sq)[:, None] >= k_pos[None, :])
    return jnp.where(mask, s, -jnp.inf)


def _lean_forward(q, k, v, causal, scale, kv_chunk):
    b, sq, h, dh = q.shape
    sk, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
    qg = q.reshape(b, sq, kvh, h // kvh, dh)
    kc, vc, n = _lean_chunks(k, v, kv_chunk)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, cidx = xs
        s = _lean_scores(qg, kb, cidx, sk=sk, causal=causal, scale=scale)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(m - m_safe)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l * corr + jnp.sum(p, axis=-1), acc), None

    shape = (b, kvh, h // kvh, sq)
    (m, l, acc), _ = jax.lax.scan(
        body, (jnp.full(shape, -jnp.inf, jnp.float32),
               jnp.zeros(shape, jnp.float32),
               jnp.zeros(shape + (dv,), jnp.float32)),
        (kc, vc, jnp.arange(n)))
    l = jnp.maximum(l, 1e-30)
    out = jnp.moveaxis(acc / l[..., None], 3, 1).reshape(b, sq, h, dv)
    return out.astype(q.dtype), m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def lean_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                   scale: float, kv_chunk: int) -> jax.Array:
    """Full-sequence attention for training: q (B,S,H,D), k (B,S,KV,D),
    v (B,S,KV,Dv) -> (B,S,H,Dv), causal or not, over KV chunks of
    ``kv_chunk``; its backward recomputes the chunks (see above)."""
    return _lean_forward(q, k, v, causal, scale, kv_chunk)[0]


def _lean_fwd(q, k, v, causal, scale, kv_chunk):
    out, lse = _lean_forward(q, k, v, causal, scale, kv_chunk)
    return out, (q, k, v, out, lse)


def _lean_bwd(causal, scale, kv_chunk, res, dout):
    q, k, v, out, lse = res
    b, sq, h, dh = q.shape
    sk, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
    grp = h // kvh
    qg = q.reshape(b, sq, kvh, grp, dh)
    dog = dout.reshape(b, sq, kvh, grp, dv).astype(q.dtype)
    delta = jnp.einsum("bqkgd,bqkgd->bkgq", dog.astype(jnp.float32),
                       out.reshape(b, sq, kvh, grp, dv).astype(jnp.float32))
    kc, vc, n = _lean_chunks(k, v, kv_chunk)

    def body(dq, xs):
        kb, vb, cidx = xs
        s = _lean_scores(qg, kb, cidx, sk=sk, causal=causal, scale=scale)
        p = jnp.exp(s - lse[..., None])
        dvb = jnp.einsum("bkgqs,bqkgd->bskd", p.astype(q.dtype), dog,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dog, vb,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds, kb,
                             preferred_element_type=jnp.float32)
        dkb = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg,
                         preferred_element_type=jnp.float32)
        return dq, (dkb.astype(k.dtype), dvb.astype(v.dtype))

    dq, (dk, dvs) = jax.lax.scan(
        body, jnp.zeros(qg.shape, jnp.float32), (kc, vc, jnp.arange(n)))

    def unchunk(x):
        return jnp.moveaxis(x, 0, 1).reshape(b, -1, kvh, x.shape[-1])[:, :sk]
    return dq.reshape(q.shape).astype(q.dtype), unchunk(dk), unchunk(dvs)


lean_attention.defvjp(_lean_fwd, _lean_bwd)


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

def _rel_shift(x: jax.Array) -> jax.Array:
    """(B,H,Sq,Sk) BD-term shift (Dai et al. 2019)."""
    b, h, sq, sk = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (1, 0)))
    x = x.reshape(b, h, sk + 1, sq)[:, :, 1:, :]
    return x.reshape(b, h, sq, sk)


@jax.named_scope("rel_shift")
def _rel_bd(qv: jax.Array, r: jax.Array) -> jax.Array:
    """The shifted BD term (B,H,Sq,Sk): on TPU one Pallas kernel that shifts
    in VMEM (kernels/xl_rel.py), per device under a mesh; elsewhere the
    product and ``_rel_shift``."""
    if jax.default_backend() != "tpu":
        return _rel_shift(jnp.einsum("bqhd,khd->bhqk", qv, r))
    mesh = current_mesh()
    if mesh is None:
        return xl_rel_bd(qv, r)
    # GSPMD cannot partition a Mosaic call, so under a mesh every device runs
    # the kernel on its own block. (batch, head) blocks are independent: the
    # batch splits as the training rules split it, the heads likewise, each
    # only where its mesh axes divide it (else that dimension stays whole).
    # check_vma=False as in core/dispatch: the transpose then psums r's
    # cotangent, the per-device partial dR, over the axes r is replicated on.
    def axes(rule, n):
        names = tuple(a for a in ((rule,) if isinstance(rule, str) else rule)
                      if a in mesh.axis_names)
        size = math.prod(mesh.shape[a] for a in names)
        return names if names and n % size == 0 else None

    b, _, h, _ = qv.shape
    batch, heads = axes(TRAIN_RULES["batch"], b), axes(TRAIN_RULES["heads"], h)
    return jax.shard_map(
        xl_rel_bd, mesh=mesh,
        in_specs=(P(batch, None, heads, None), P(None, heads, None)),
        out_specs=P(batch, heads, None, None), check_vma=False)(qv, r)


def xl_attention(params: Dict, q: jax.Array, k: jax.Array, v: jax.Array,
                 cfg: AttentionConfig, d_model: int) -> jax.Array:
    """q (B,Sq,H,D); k/v (B,Sk,H,D) where Sk = mem + Sq. Full (small-ctx) scores."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    scale = cfg.softmax_scale or (dh ** -0.5)
    r = sinusoid_positions(sk, d_model, q.dtype)[::-1]        # distances sk-1..0
    r = (r @ params["w_r"].astype(q.dtype)).reshape(sk, h, dh)
    ac = jnp.einsum("bqhd,bkhd->bhqk", q + params["u_bias"].astype(q.dtype), k)
    bd = _rel_bd(q + params["v_bias"].astype(q.dtype), r)
    s = (ac + bd).astype(jnp.float32) * scale
    q_pos = (sk - sq) + jnp.arange(sq)
    mask = q_pos[:, None] >= jnp.arange(sk)[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2 Sec. 2.1)
# ---------------------------------------------------------------------------

@jax.named_scope("mla_latent")
def mla_qkv(params: Dict, x: jax.Array, cfg: ModelConfig,
            positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Queries, keys and values of MLA: the latent down-projection, its
    RMSNorm, the up-projection and the decoupled RoPE. q, k (B,S,H,nope+rope)
    with the rope part last (the key's shared by every head), v (B,S,H,v)."""
    a = cfg.attention
    b, s, _ = x.shape
    r, rope = a.kv_lora_rank, a.rope_head_dim
    nope = a.head_dim - rope
    q = _split_heads(jnp.einsum("bsd,dq->bsq", x, params["wq"].astype(x.dtype)),
                     a.n_heads, a.head_dim)
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(x.dtype))
    c = rms_norm_simple(kv[..., :r], params["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, r:], positions, a.rope_theta)
    kvb = _split_heads(
        jnp.einsum("bsr,rq->bsq", c, params["wkv_b"].astype(x.dtype)),
        a.n_heads, nope + a.v_head_dim)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], positions, a.rope_theta)],
        axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (b, s, a.n_heads, rope))],
        axis=-1)
    return q, k, kvb[..., nope:]


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
    if kind == "mla":
        if cache is not None or memory is not None or cross_kv is not None:
            raise NotImplementedError("mla attention runs full sequences "
                                      "only (no KV cache, memory or cross)")
        if positions is None:
            positions = jnp.arange(s)
        q, k, v = mla_qkv(params, x, cfg, positions)
        out = lean_attention(q, k, v, a.causal, scale, a.kv_chunk)
        y = jnp.einsum("bsq,qd->bsd", out.reshape(b, s, a.o_dim),
                       params["wo"].astype(x.dtype))
        return y, None

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
