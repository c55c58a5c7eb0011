"""Step builders: jit-able train / prefill / decode steps with mixed precision,
gradient accumulation, clipping, compression and LR scheduling baked in.

The returned functions are pure (state, batch, rng) -> (state, metrics) and carry
*all* mutable training state in one pytree, so checkpointing and restart are exact.

Pod-tier gradient compression (multi-host wiring)
-------------------------------------------------
When ``make_train_step`` is given a mesh with a 'pod' axis of size > 1 and
``grad_compression != "none"``, the step stops treating compression as a
host-local roundtrip on the reduced gradient and instead wires it into the
cross-pod (DCN) reduction itself:

  1. the global batch is split along the 'pod' axis and each pod slice's
     PARTIAL gradient is computed separately (a scan over pod slices — the
     same microbatching machinery as grad_accum, so the two compose: each
     pod slice still microbatches internally);
  2. the stacked (pod, ...) partials are sharding-constrained onto the 'pod'
     mesh axis, so the ONLY cross-pod gradient traffic in the compiled
     program is the mean over that leading dim;
  3. that mean goes through ``optim.compress_pod_grads``: expert-parameter
     leaves are int8/bf16 error-feedback quantized PER POD before the
     reduction (the wire values are what crosses DCN), dense trunk leaves
     take the exact mean. Residuals are per-pod ((pod, ...) 'err' leaves,
     sharded over 'pod' by the 'pod_err' logical rule).

Ordering note: the legacy path clips then compresses the reduced gradient;
the pod path necessarily compresses DURING the reduction and clips after —
clipping a not-yet-reduced partial would need a second cross-pod collective
for the global norm. XL memory (``xl_memory``) is not supported on the pod
path (its state is batch-minor); request one or the other.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import OptimizerConfig
from ..models.lm import LM
from ..optim import (adamw_init, adamw_update, clip_by_global_norm, compress_grads,
                     compress_pod_grads, init_compression_state, make_schedule)


def _pod_size(mesh) -> int:
    if mesh is None or "pod" not in getattr(mesh, "axis_names", ()):
        return 1
    return mesh.shape["pod"]


def init_train_state(model: LM, key, opt_cfg: OptimizerConfig,
                     use_mems: bool = False, batch: int = 0,
                     pod: int = 1) -> Dict[str, Any]:
    """``pod``: size of the mesh's DCN 'pod' axis (1 = no pod tier). With
    pod > 1 and compression on, the error-feedback state is per-pod (leading
    pod dim on expert leaves — see module header)."""
    params = model.init(key)
    state = {"params": params, "opt": adamw_init(params)}
    if opt_cfg.grad_compression != "none":
        state["err"] = init_compression_state(params, pod=pod)
    if use_mems and model.cfg.xl_memory:
        from ..models.stack import init_mems
        state["mems"] = init_mems(model.cfg, batch, model.dtype)
    return state


def make_train_step(model: LM, opt_cfg: OptimizerConfig,
                    grad_accum: int = 1, mesh=None):
    sched = make_schedule(opt_cfg)
    use_mems = bool(model.cfg.xl_memory)
    pod = _pod_size(mesh)
    pod_tier = pod > 1 and opt_cfg.grad_compression != "none"
    if pod_tier and use_mems:
        raise NotImplementedError(
            "pod-tier gradient compression does not support xl_memory "
            "(mems state is batch-minor; slicing it per pod is unsupported)")

    def loss_for(params, batch, rng, mems):
        out = model.loss(params, batch, rng=rng, train=True, mems=mems)
        loss, aux = out
        if use_mems:
            metrics, new_mems = aux
        else:
            metrics, new_mems = aux, None
        return loss, (metrics, new_mems)

    def compute_grads(params, batch, rng, mems):
        if grad_accum <= 1:
            (loss, (metrics, new_mems)), grads = jax.value_and_grad(
                loss_for, has_aux=True)(params, batch, rng, mems)
            return loss, metrics, new_mems, grads

        # microbatching: scan over grad_accum slices, accumulate fp32 grads.
        def micro(carry, xs):
            acc, mems_c = carry
            mb, r = xs
            (loss, (metrics, new_mems)), grads = jax.value_and_grad(
                loss_for, has_aux=True)(params, mb, r, mems_c)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) / grad_accum, acc, grads)
            return (acc, new_mems if use_mems else mems_c), (loss, metrics)

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum) + x.shape[1:]),
            batch)
        rngs = jax.random.split(rng, grad_accum)
        (grads, new_mems), (losses, metricss) = jax.lax.scan(
            micro, (zeros, mems), (mbs, rngs))
        loss = jnp.mean(losses)
        metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, 0), metricss)
        return loss, metrics, (new_mems if use_mems else None), grads

    def pod_partial_grads(params, batch, rng):
        """Per-pod partial gradients: scan over pod slices of the batch, each
        slice running the full compute_grads (grad_accum microbatching and the
        MoE dispatch path — including the EP shard_map — compose unchanged).
        Returns (loss, metrics, stacked (pod, ...) grads) with the stack
        sharding-constrained onto the 'pod' mesh axis so the downstream mean
        is the cross-pod all-reduce."""
        def one_pod(_, xs):
            mb, r = xs
            loss, metrics, _, grads = compute_grads(params, mb, r, None)
            return None, (loss, metrics, grads)

        b = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if b % pod:
            raise ValueError(f"pod-tier compression needs the global batch "
                             f"({b}) divisible by the pod axis ({pod})")
        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((pod, x.shape[0] // pod) + x.shape[1:]), batch)
        rngs = jax.random.split(rng, pod)
        _, (losses, metricss, grads_pp) = jax.lax.scan(
            one_pod, None, (mbs, rngs))
        if mesh is not None:
            pod_sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("pod"))
            grads_pp = jax.tree_util.tree_map(
                lambda g: jax.lax.with_sharding_constraint(g, pod_sh), grads_pp)
        loss = jnp.mean(losses)
        metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, 0), metricss)
        return loss, metrics, grads_pp

    def train_step(state: Dict[str, Any], batch: Dict, rng) -> Tuple[Dict, Dict]:
        params = state["params"]
        mems = state.get("mems")
        rng = jax.random.fold_in(rng, state["opt"].step)
        new_state = dict(state)
        if pod_tier:
            # Multi-host wiring: compress the expert subtree INSIDE the
            # cross-pod reduction (see module header), then clip the reduced
            # gradient.
            loss, metrics, grads_pp = pod_partial_grads(params, batch, rng)
            grads, new_err = compress_pod_grads(grads_pp, state["err"],
                                                opt_cfg.grad_compression)
            new_state["err"] = new_err
            new_mems = None
        else:
            loss, metrics, new_mems, grads = compute_grads(params, batch, rng,
                                                           mems)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
            if "err" in state and not pod_tier:
                grads, new_err = compress_grads(grads, state["err"],
                                                opt_cfg.grad_compression)
                new_state["err"] = new_err
            lr = sched(state["opt"].step)
            new_params, new_opt = adamw_update(grads, state["opt"], params,
                                               opt_cfg, lr)
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        if new_mems is not None:
            new_state["mems"] = new_mems
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return train_step


def make_eval_step(model: LM):
    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch, rng=None, train=False)
        return loss, metrics
    return eval_step


def make_prefill_step(model: LM, max_len: int):
    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache)
    return prefill_step


def make_decode_step(model: LM):
    def decode_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)
    return decode_step
