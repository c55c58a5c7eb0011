"""Expert selection functions (paper Sec. 3.3-5).

All selectors share the contract::

    gates, idx, info = select_experts(logits, cfg, rng=..., train=...)

where ``logits = x @ W3`` (+ optional noise net), ``gates`` are the (N, K) weighting
scores s[e] of Eq. 11, ``idx`` the (N, K) selected expert indices, and ``info`` carries
the full selection distribution used by the regularizers.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import FFNConfig


class SelectionInfo(NamedTuple):
    probs: jax.Array        # (N, E) softmax(W3 x) -- Eq. 20 (always softmax)
    sel: jax.Array          # (N, E) the actual selector activation output
    idx: jax.Array          # (N, K)
    gates: jax.Array        # (N, K)


def norm_topk(s: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Paper Eqs. 23-25: keep top-K of s, renormalize to sum 1. Returns (gates, idx)."""
    vals, idx = jax.lax.top_k(s, k)
    gates = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
    return gates, idx


def two_stage_topk(ua: jax.Array, ub: jax.Array, k: int,
                   n_candidates: int = 0
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Two-stage product-key top-K (paper Sec. 3.2 / Lample et al. 2019).

    The full score grid is u[i] = ub[i // ns] + ua[i mod ns] over
    n_values = ns**2 entries; this never materializes it. Stage 1 takes the
    top-C of each half independently; stage 2 re-scores only the C*C
    candidate grid and takes the final top-K. For C >= K the true top-K of
    the full grid is provably contained in the candidate grid (each of the
    true top-K has both halves in their respective top-K <= top-C), so the
    result is exact while the work is O(ns + C^2) per token instead of
    O(ns^2) — `n_values` can reach 1M+ (ns=1024) without a
    (n_tokens, n_values) score matrix ever existing.

    ua, ub: (..., ns) sub-key score halves. Returns ``(scores, sel_a, sel_b)``
    each (..., K), where the flat value index is ``sel_b * ns + sel_a``.
    """
    c = n_candidates or k
    va, ia = jax.lax.top_k(ua, c)
    vb, ib = jax.lax.top_k(ub, c)
    cand = va[..., :, None] + vb[..., None, :]            # (..., C, C)
    cand = cand.reshape(*cand.shape[:-2], c * c)
    top, flat = jax.lax.top_k(cand, k)                    # over C*C, not ns*ns
    sel_a = jnp.take_along_axis(ia, flat // c, axis=-1)
    sel_b = jnp.take_along_axis(ib, flat % c, axis=-1)
    return top, sel_a, sel_b


def sinkhorn(logits: jax.Array, n_iters: int = 8) -> jax.Array:
    """Log-space Sinkhorn normalization (Clark et al. 2022 S-BASE routing).

    Returns a (N, E) soft assignment matrix whose columns are balanced: each expert
    receives ~N/E total mass. Rows sum to 1.
    """
    n, e = logits.shape
    f = jnp.zeros((n, 1), logits.dtype)   # row potentials
    g = jnp.zeros((1, e), logits.dtype)   # col potentials
    # target marginals: rows sum 1, cols sum N/E
    log_row = jnp.zeros((n, 1), logits.dtype)
    log_col = jnp.full((1, e), jnp.log(n / e), logits.dtype)

    def body(_, fg):
        f, g = fg
        g = log_col - jax.nn.logsumexp(logits + f, axis=0, keepdims=True)
        f = log_row - jax.nn.logsumexp(logits + g, axis=1, keepdims=True)
        return f, g

    f, g = jax.lax.fori_loop(0, n_iters, body, (f, g))
    return jnp.exp(logits + f + g)


def expert_dropout_mask(rng: jax.Array, n_experts: int, rate: float) -> jax.Array:
    """Paper Eq. 22: Bernoulli(1-delta) mask over whole experts, NO rescaling."""
    return jax.random.bernoulli(rng, 1.0 - rate, (n_experts,))


def select_experts(logits: jax.Array, cfg: FFNConfig, *,
                   rng: Optional[jax.Array] = None, train: bool = False,
                   noise_logits: Optional[jax.Array] = None,
                   n_valid_experts: Optional[int] = None,
                   bias: Optional[jax.Array] = None) -> SelectionInfo:
    """Dispatch over the paper's selector variants.

    logits: (N, E_padded) = x @ W3.
    noise_logits: (N, E) = x @ W4, only for the Shazeer noisy-top-K variant.
    n_valid_experts: real expert count; experts >= this are padding (masked out).
    bias: (E,) DeepSeek-V3 selection bias: the top-K is taken over sel + bias,
    the gates are the chosen experts' sel alone.
    """
    n, e = logits.shape
    k = cfg.k
    neg = jnp.asarray(-1e9, logits.dtype)
    if n_valid_experts is not None and n_valid_experts < e:
        valid = jnp.arange(e) < n_valid_experts
        logits = jnp.where(valid[None, :], logits, neg)

    # Shazeer noisy gating (Eq. 13): add N(0,1)*softplus(W4 x) during training.
    if noise_logits is not None and train and rng is not None:
        rng, nrng = jax.random.split(rng)
        noise = jax.random.normal(nrng, logits.shape, logits.dtype)
        logits = logits + noise * jax.nn.softplus(noise_logits)

    probs = jax.nn.softmax(logits, axis=-1)            # Eq. 20 (regularizer input)

    act = cfg.selector_activation
    if act == "sigmoid":
        sel = jax.nn.sigmoid(logits)
    elif act in ("softmax", "softmax_pre_topk"):
        sel = probs
    else:
        raise ValueError(f"unknown selector activation {act}")

    # Expert dropout (sigma-MoE, Eq. 22): multiply sel by a per-expert mask.
    if train and cfg.expert_dropout > 0.0 and rng is not None:
        rng, drng = jax.random.split(rng)
        mask = expert_dropout_mask(drng, e, cfg.expert_dropout)
        sel = sel * mask[None, :].astype(sel.dtype)

    if act == "softmax_pre_topk" or (act == "softmax" and cfg.renormalize):
        # Footnote 4: renormalizing after top-K == top-K on logits before softmax.
        gates, idx = norm_topk(sel, k)
    else:
        if bias is None:
            gates, idx = jax.lax.top_k(sel, k)
        else:
            _, idx = jax.lax.top_k(sel + bias.astype(sel.dtype), k)
            gates = jnp.take_along_axis(sel, idx, axis=-1)
        if cfg.renormalize:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale

    return SelectionInfo(probs=probs, sel=sel, idx=idx, gates=gates)


def select_experts_sbase(logits: jax.Array, cfg: FFNConfig, *, train: bool = False,
                         n_valid_experts: Optional[int] = None) -> SelectionInfo:
    """S-BASE (Clark et al. 2022, as reimplemented by the paper Sec. 4).

    Training: Sinkhorn-balance the scores, route by the balanced matrix's top-K;
    weighting score is always sigmoid(logits) (Eq. 18). Eval: plain top-K of sigmoid.
    """
    n, e = logits.shape
    neg = jnp.asarray(-1e9, logits.dtype)
    if n_valid_experts is not None and n_valid_experts < e:
        valid = jnp.arange(e) < n_valid_experts
        logits = jnp.where(valid[None, :], logits, neg)
    sel = jax.nn.sigmoid(logits)
    probs = jax.nn.softmax(logits, axis=-1)
    if train:
        pi = sinkhorn(logits.astype(jnp.float32), cfg.sinkhorn_iters).astype(logits.dtype)
        if n_valid_experts is not None and n_valid_experts < e:
            pi = jnp.where((jnp.arange(e) < n_valid_experts)[None, :], pi, 0.0)
        _, idx = jax.lax.top_k(pi, cfg.k)
        gates = jnp.take_along_axis(sel, idx, axis=-1)
    else:
        gates, idx = jax.lax.top_k(sel, cfg.k)
    return SelectionInfo(probs=probs, sel=sel, idx=idx, gates=gates)
