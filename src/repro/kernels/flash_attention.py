"""Pallas TPU flash-attention (forward) kernel.

Grid: (batch*kv_heads*q_groups, q_blocks); the kernel body runs an online-softmax
loop over KV blocks held in VMEM. Blocks are MXU-aligned (BQ x D, BK x D); the
(BQ, BK) probability tile never leaves VMEM — the memory behaviour the pure-JAX
chunked path (models/attention.py) emulates at the XLA level.

Backward uses the differentiable pure-JAX path via custom_vjp (recompute-based, the
standard flash trade). ops-level entry: ``flash_fwd`` in kernels/ops.py style —
here self-contained as ``flash_attention_pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128
BK = 128
NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
            kv_len: int):
    # q_ref: (1, BQ, D); k_ref/v_ref: (1, SK, D); o_ref: (1, BQ, D)
    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    sk = k_ref.shape[1]
    n_kb = sk // BK

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(kb * BK, BK), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(kb * BK, BK), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (BQ, BK)
        q_pos = qb * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
        k_pos = kb * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((BQ,), NEG, jnp.float32)
    l0 = jnp.zeros((BQ,), jnp.float32)
    a0 = jnp.zeros((BQ, q_ref.shape[2]), jnp.float32)
    # causal: KV blocks beyond this Q block contribute nothing; skip them.
    upper = n_kb if not causal else jnp.minimum(
        n_kb, (qb + 1) * BQ // BK + (1 if BQ % BK else 0)).astype(jnp.int32)
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, scale: float,
                           interpret: bool = False) -> jax.Array:
    """q (B, Sq, H, D), k/v (B, Sk, KV, D) with H % KV == 0; Sq/Sk padded to 128
    internally. Forward only (wrap with custom_vjp at the call site if training)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    grp = h // kvh
    from ..common import round_up
    sq_p, sk_p = round_up(sq, BQ), round_up(sk, BK)
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    # layout: (B*H, S, D) with q head order grouped by kv head
    qf = qp.reshape(b, sq_p, kvh, grp, d).transpose(0, 2, 3, 1, 4) \
           .reshape(b * kvh * grp, sq_p, d)
    kf = kp.transpose(0, 2, 1, 3).reshape(b * kvh, sk_p, d)
    vf = vp.transpose(0, 2, 1, 3).reshape(b * kvh, sk_p, d)

    grid = (b * kvh * grp, sq_p // BQ)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, kv_len=sk),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i // grp, 0, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i // grp, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh * grp, sq_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf[:, None].reshape(b * kvh * grp, sq_p, d), kf, vf)
    out = out.reshape(b, kvh, grp, sq_p, d).transpose(0, 3, 1, 2, 4) \
             .reshape(b, sq_p, h, d)
    return out[:, :sq]
