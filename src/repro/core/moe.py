"""sigma-MoE and baseline MoE variants (paper Sec. 3.3-5): parameters + routing.

This module owns what is MoE-*specific* — expert/selector initialization
(paper Sec. 5 init), the routing front-end (routing.py selectors at the
layer's logits), shared always-on experts, and the regularizer bookkeeping.
The selection -> dispatch -> execution machinery lives in core/dispatch.py
(``dispatch.expert_mlp``), shared with every other approximator in the
paper's framework: the three dispatch paths ("sort" dropless CVMM, "einsum"
GShard capacity under pjit, "shard_map" explicit all_to_all EP) and the
kernel capability chain (pallas_fused -> pallas -> ragged) are resolved
there, in one place. ``apply_moe`` is routing + one call into that layer.

All paths share the routing math (routing.py), regularizers (regularizers.py)
and the paper's initialization (init.py), so ablations isolate exactly one
design choice.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import act_fn, round_up
from ..configs.base import FFNConfig
from . import init as initlib
from .dispatch import expert_mlp
from .regularizers import REGULARIZERS, usage_stats
from .routing import SelectionInfo, select_experts, select_experts_sbase


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def n_experts_padded(cfg: FFNConfig, ep_degree: int = 0) -> int:
    if ep_degree and cfg.n_experts % ep_degree:
        return round_up(cfg.n_experts, ep_degree)
    return cfg.n_experts


def init_moe(key, d_model: int, cfg: FFNConfig, n_layers: int,
             dtype=jnp.float32, ep_degree: int = 0) -> Dict:
    """Expert + selector parameters.

    sigma_moe_init=True (paper Sec. 5): W1/W2 stds use d_model/d_ff (the DENSE
    equivalent), W3 row-normalized at W1's std. False: 'standard init' ablation,
    std from per-expert fan-in G.
    """
    e = n_experts_padded(cfg, ep_degree)
    g = cfg.expert_size
    d_ff = cfg.n_router * g
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    if cfg.sigma_moe_init:
        s1 = initlib.dense_std_in(d_model, n_layers)
        s2 = initlib.dense_std_out(d_ff, n_layers)
    else:
        s1 = (d_model) ** -0.5
        s2 = (0.1 / g) ** 0.5          # Switch Transformer's sqrt(0.1/G)
    p = {
        "we1": initlib.normal(k1, (e, d_model, g), s1, dtype),
        "we2": initlib.normal(k2, (e, g, d_model), s2, dtype),
        "router": initlib.row_normalized(k3, (cfg.n_router, d_model), s1, dtype).T
              if cfg.sigma_moe_init else
              initlib.normal(k3, (d_model, cfg.n_router), s1, dtype),
    }
    if cfg.bias_rate:
        # the selection bias: moved by the train step, never by a gradient
        p["router_bias"] = {"bias": jnp.zeros((cfg.n_router,), dtype)}
    if cfg.glu_experts:
        p["we1g"] = initlib.normal(k4, (e, d_model, g), s1, dtype)
    if cfg.kind == "noisy_topk":
        p["router_noise"] = initlib.normal(k5, (d_model, cfg.n_experts), s1, dtype)
    if cfg.n_shared_experts:
        ks1, ks2, ks3 = jax.random.split(k6, 3)
        se = cfg.n_shared_experts
        p["shared_w1"] = initlib.normal(ks1, (se, d_model, g), s1, dtype)
        p["shared_w2"] = initlib.normal(ks2, (se, g, d_model), s2, dtype)
        if cfg.glu_experts:
            p["shared_w1g"] = initlib.normal(ks3, (se, d_model, g), s1, dtype)
    return p


# ---------------------------------------------------------------------------
# Routing front-end (shared by all dispatch paths)
# ---------------------------------------------------------------------------

def _route(params: Dict, xf: jax.Array, cfg: FFNConfig, rng, train: bool,
           e_pad: int) -> SelectionInfo:
    if cfg.bias_rate:
        # DeepSeek-V3's router scores in float32: its bias moves in steps of
        # bias_rate, finer than bfloat16 resolves next to the scores
        xf = xf.astype(jnp.float32)
    logits = jnp.einsum("nd,de->ne", xf, params["router"].astype(xf.dtype))
    if e_pad > cfg.n_router:
        pad = jnp.full((xf.shape[0], e_pad - cfg.n_router), -1e9, logits.dtype)
        logits = jnp.concatenate([logits, pad], axis=-1)
    if cfg.kind == "sbase":
        return select_experts_sbase(logits, cfg, train=train,
                                    n_valid_experts=cfg.n_router)
    noise_logits = None
    if cfg.kind == "noisy_topk":
        noise_logits = jnp.einsum("nd,de->ne", xf, params["router_noise"].astype(xf.dtype))
        if e_pad > cfg.n_experts:
            noise_logits = jnp.pad(noise_logits,
                                   ((0, 0), (0, e_pad - cfg.n_experts)))
    bias = (jax.lax.stop_gradient(params["router_bias"]["bias"])
            if cfg.bias_rate else None)
    return select_experts(logits, cfg, rng=rng, train=train,
                          noise_logits=noise_logits, n_valid_experts=cfg.n_router,
                          bias=bias)


def held_share(info: SelectionInfo, cfg: FFNConfig) -> SelectionInfo:
    """The rows this layer's held experts compute: expert ids made local to
    the share [expert_offset, expert_offset + n_experts); rows routed to an
    expert held elsewhere get the id n_experts and gate 0, and the sort
    dispatch leaves them out."""
    local = info.idx - cfg.expert_offset
    held = (local >= 0) & (local < cfg.n_experts)
    return info._replace(idx=jnp.where(held, local, cfg.n_experts),
                         gates=jnp.where(held, info.gates, 0.0))


def update_selection_bias(bias: jax.Array, load: jax.Array,
                          rate: float) -> jax.Array:
    """DeepSeek-V3's auxiliary-loss-free balancing: each expert's bias moves
    by rate toward the mean load (up when under it, down when over)."""
    return bias + rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True)
                                  - load).astype(bias.dtype)


# ---------------------------------------------------------------------------
# Public apply: routing + the shared execution layer
# ---------------------------------------------------------------------------

@jax.named_scope("moe")
def apply_moe(params: Dict, x: jax.Array, cfg: FFNConfig, *,
              rng: Optional[jax.Array] = None, train: bool = False,
              collect_stats: bool = False) -> Tuple[jax.Array, Dict]:
    """y_hat = sum_{e in E_x} W2^e s[e] act(W1^e x)   (paper Eq. 11) + aux losses."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    e = params["we1"].shape[0]                             # possibly padded

    with jax.named_scope("router"):
        info = _route(params, xf, cfg, rng, train, max(e, cfg.n_router))
        routed = held_share(info, cfg) if cfg.holds_share else info
    y, dropped = expert_mlp(params, xf, cfg, routed, e)

    if cfg.n_shared_experts:
        act = act_fn(cfg.activation)
        hs = jnp.einsum("nd,edg->eng", xf, params["shared_w1"].astype(xf.dtype))
        us = act(hs)
        if cfg.glu_experts:
            us = us * jnp.einsum("nd,edg->eng", xf,
                                 params["shared_w1g"].astype(xf.dtype))
        y = y + jnp.einsum("eng,egd->nd", us, params["shared_w2"].astype(xf.dtype))

    if cfg.reg_kind == "seq_balance":
        n_seq = lead[0] if len(lead) > 1 else 1
        reg = REGULARIZERS[cfg.reg_kind](info, cfg.n_router, n_seq)
    else:
        reg = REGULARIZERS[cfg.reg_kind](info, cfg.n_experts)
    aux = {"moe_reg": cfg.reg_gamma * reg, "moe_dropped": dropped}
    if cfg.bias_rate:
        aux["load"] = jnp.sum(jax.nn.one_hot(info.idx, cfg.n_router,
                                             dtype=jnp.float32), axis=(0, 1))
        aux["moe_rows_held"] = jnp.sum(
            (routed.idx < cfg.n_experts).astype(jnp.float32))
        # the sort plan's live row tiles and all its tiles (kernels/ops.py)
        from ..kernels import ops as kops
        gs = jnp.bincount(routed.idx.reshape(-1), length=e)
        aux["moe_tiles_live"] = kops.live_tiles(gs)[0].astype(jnp.float32)
        aux["moe_tiles"] = jnp.float32(kops.grid_tiles(routed.idx.size, e))
    if collect_stats:
        aux["usage"] = usage_stats(info, cfg.n_router)
    return y.reshape(*lead, d), aux
