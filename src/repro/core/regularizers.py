"""Load-balancing regularizers (paper Sec. 4-5).

Each takes the SelectionInfo of one MoE layer and returns a scalar loss (to be
*added*, already sign-correct for minimization).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .routing import SelectionInfo


def entropy_reg(info: SelectionInfo, n_valid: int) -> jax.Array:
    """sigma-MoE (Eqs. 20-21): L = sum_e p[e] log p[e], p = batch-mean softmax.

    Minimizing L maximizes the entropy of the mean selection distribution.
    """
    p = jnp.mean(info.probs.astype(jnp.float32), axis=0)[:n_valid]
    return jnp.sum(p * jnp.log(p + 1e-9))


def switch_reg(info: SelectionInfo, n_valid: int) -> jax.Array:
    """Switch Transformer (Eqs. 15-17): L = N_E * f . p  with hard routing fraction f."""
    n, e = info.probs.shape
    k = info.idx.shape[-1]
    onehot = jax.nn.one_hot(info.idx, e, dtype=jnp.float32)       # (N, K, E)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)                 # (E,)
    p = jnp.mean(info.probs.astype(jnp.float32), axis=0)
    return n_valid * jnp.sum((f * p)[:n_valid]) / k


def cv_reg(info: SelectionInfo, n_valid: int) -> jax.Array:
    """Sparsely-Gated MoE (Eq. 14): CV^2 of total normalized-top-K importance."""
    n, e = info.probs.shape
    onehot = jax.nn.one_hot(info.idx, e, dtype=jnp.float32)
    imp = jnp.sum(onehot * info.gates.astype(jnp.float32)[..., None], axis=(0, 1))
    imp = imp[:n_valid]
    mean = jnp.mean(imp)
    var = jnp.var(imp)
    return var / (mean * mean + 1e-9)


def seq_balance_reg(info: SelectionInfo, n_valid: int,
                    n_seq: int = 1) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss (Eqs. 17-20): per sequence of
    T tokens, sum_e f[e] P[e] with f[e] = E / (K T) * (tokens choosing e) and
    P[e] the mean over tokens of the scores normalized over experts; the mean
    over the batch's ``n_seq`` sequences."""
    k = info.idx.shape[-1]
    s = info.sel.astype(jnp.float32)[:, :n_valid].reshape(n_seq, -1, n_valid)
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
    chosen = jnp.sum(jax.nn.one_hot(info.idx, n_valid, dtype=jnp.float32),
                     axis=1).reshape(n_seq, -1, n_valid)
    f = jnp.sum(chosen, axis=1) * (n_valid / (k * chosen.shape[1]))
    return jnp.mean(jnp.sum(f * p, axis=-1))


REGULARIZERS = {"entropy": entropy_reg, "switch": switch_reg, "cv": cv_reg,
                "seq_balance": seq_balance_reg,
                "none": lambda info, n_valid: jnp.float32(0.0)}


def usage_stats(info: SelectionInfo, n_valid: int):
    """Diagnostics for expert-collapse analysis (paper Fig. 3/7)."""
    n, e = info.probs.shape
    onehot = jax.nn.one_hot(info.idx, e, dtype=jnp.float32)
    counts = jnp.sum(onehot, axis=(0, 1))[:n_valid]
    weight = jnp.sum(onehot * info.gates.astype(jnp.float32)[..., None],
                     axis=(0, 1))[:n_valid]
    frac = counts / (jnp.sum(counts) + 1e-9)
    ent = -jnp.sum(frac * jnp.log(frac + 1e-9))
    return {"counts": counts, "weight": weight, "usage_entropy": ent}
