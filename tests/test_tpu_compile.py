"""Ahead-of-time compiles of the hot-path kernels for a described TPU v5e.

Interpret mode runs the Pallas kernels on the CPU and accepts things the
chip's compiler refuses (a block or DMA slice that is not aligned to the
(8, 128) tiling, a scratch that overflows VMEM). These tests compile the real
kernels with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology at the published widths of the models the repo runs, and check that
each compiled program holds the Mosaic kernel (``tpu_custom_call``). Nothing
runs: the TPU compiler is installed with jaxlib and needs no chip.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# (d_model, expert_size, n_experts, k, glu, activation, n_tokens)
WIDTHS = {
    # paper Tab. 8/9: 262M sigma-MoE, batch 8 x 512 tokens
    "wt103-262m-moe": (1024, 128, 32, 4, False, "relu", 8 * 512),
    # granite 3.0 MoE: 40 GLU experts of 512, top-8; one 2048-token prefill
    "granite-moe-3b-a800m": (1536, 512, 40, 8, True, "silu", 2048),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An AOT compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _moe_args(sharding, name, dtype):
    d, g, e, k, glu, act, n = WIDTHS[name]
    args = [_spec(sharding, (n, d), dtype),
            _spec(sharding, (n, k), jnp.int32),
            _spec(sharding, (n, k), jnp.float32),
            _spec(sharding, (e, d, g), dtype),
            _spec(sharding, (e, g, d), dtype)]
    if glu:
        args.append(_spec(sharding, (e, d, g), dtype))
    return args, act, n, e


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("train", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("name", sorted(WIDTHS))
def test_fused_moe_mlp_compiles(one_chip, name, train, dtype):
    args, act, n, e = _moe_args(one_chip, name, dtype)
    glu = WIDTHS[name][4]

    def fwd(x, idx, gates, w1, w2, w1g=None):
        plan = ops.make_moe_plan(idx, gates, n, e)
        return ops.moe_mlp_fused(x, plan, w1, w2, w1g, activation=act,
                                 interpret=False)

    def loss(x, idx, gates, w1, w2, w1g=None):
        return jnp.sum(fwd(x, idx, gates, w1, w2, w1g).astype(jnp.float32))

    def fwd_bwd(x, idx, gates, w1, w2, *w1g):
        return jax.grad(loss, argnums=(0, 2, 3, 4) + (5,) * bool(w1g))(
            x, idx, gates, w1, w2, *w1g)

    text = _compile(fwd_bwd if train else fwd, *args)
    # forward: fused w1 + fused w2. Under grad the forward w2 is dead (the
    # loss is linear in it) and the backward adds t0, the streamed dW1/dW2
    # (+dW1g) and the dX grouped GEMM(s).
    assert text.count("tpu_custom_call") >= ((5 + 2 * glu) if train else 2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_dedup_weighted_gather_compiles(one_chip, dtype):
    # PKM at the 262M model's width: 4 heads x top-32 over 256**2 values,
    # 4096 tokens.
    n, d, n_values, s = 4096, 1024, 256 * 256, 4 * 32

    def fn(values, idx, w):
        plan = ops.make_dedup_gather_plan(idx, w, n_values)
        return ops.gathered_weighted_sum_dedup(values, plan, n,
                                               interpret=False)

    text = _compile(fn, _spec(one_chip, (n_values, d), dtype),
                    _spec(one_chip, (n, s), jnp.int32),
                    _spec(one_chip, (n, s), jnp.float32))
    assert "tpu_custom_call" in text


def test_moe_mlp_decode_compiles(one_chip):
    d, g, e, k, glu, act, _ = WIDTHS["granite-moe-3b-a800m"]
    lanes, dtype = 8, jnp.bfloat16
    plan = ops.make_decode_plan(lanes, k, e, d, g, dtype)
    assert plan is not None

    def fn(x, idx, gates, w1, w2, w1g):
        return ops.moe_mlp_decode(x, idx, gates, plan, w1, w2, w1g,
                                  activation=act, interpret=False)

    text = _compile(fn, _spec(one_chip, (lanes, d), dtype),
                    _spec(one_chip, (lanes, k), jnp.int32),
                    _spec(one_chip, (lanes, k), jnp.float32),
                    _spec(one_chip, (e, d, g), dtype),
                    _spec(one_chip, (e, g, d), dtype),
                    _spec(one_chip, (e, d, g), dtype))
    # row gather + w1 + w1g + w2
    assert text.count("tpu_custom_call") >= 4
