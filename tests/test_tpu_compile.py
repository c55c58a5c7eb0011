"""Ahead-of-time compiles of the hot-path kernels for a described TPU v5e.

Interpret mode runs the Pallas kernels on the CPU and accepts things the
chip's compiler refuses (a block or DMA slice that is not aligned to the
(8, 128) tiling, a scratch that overflows VMEM). These tests compile the real
kernels with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology at the published widths of the models the repo runs, and check that
each compiled program holds the Mosaic kernel (``tpu_custom_call``). The
last tests compile a cut-down train step and check the layer scopes and
kernel names in its op names. Nothing runs: the TPU compiler is installed
with jaxlib and needs no chip.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
from __future__ import annotations

import dataclasses
import os
import re

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
def v5e_2x2():
    """The devices of a described v5e:2x2 (nothing runs on them)."""
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
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


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


# (batch, heads, head_dim, queries, keys) of xl_rel attention in training:
# rows of 512 + 1 tokens over 512 of memory, and 256 + 1 over 256.
XL_REL_WIDTHS = {
    "wt103-262m": (16, 16, 64, 513, 1025),
    "wt103-47m": (16, 10, 41, 257, 513),
}


@pytest.mark.parametrize("train", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("name", sorted(XL_REL_WIDTHS))
def test_xl_rel_bd_compiles(one_chip, name, train):
    from repro.kernels.xl_rel import xl_rel_bd
    b, h, d, sq, sk = XL_REL_WIDTHS[name]

    def loss(qv, r):
        return jnp.sum(xl_rel_bd(qv, r).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1)) if train else xl_rel_bd
    text = _compile(fn, _spec(one_chip, (b, sq, h, d), jnp.bfloat16),
                    _spec(one_chip, (sk, h, d), jnp.bfloat16))
    # under grad the forward kernel is dead (the loss is linear in it)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ("xl_rel_bd_bwd" in text) == train


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


# ---------------------------------------------------------------------------
# The train step names its layers and its kernels in the program.
#
# Each layer boundary of the step opens a ``jax.named_scope`` (``embed``,
# ``attention``, ``moe``, ``loss``, ``optimizer``, and ``block`` for norms and
# residual adds) and every Pallas call carries a kernel name; both reach the
# op-name metadata of the compiled step, which a device trace records as each
# op's ``tf_op`` and the benchmark sums device time by (chipbench/scopes.py).
# A refactor that drops one would turn a per-layer metric null. The step is
# wt103-262m-moe cut to 2 layers at its published widths (XL memory 128,
# batch 4 x 128), with the sort dispatch on the fused kernels and full remat.
# ---------------------------------------------------------------------------

LAYERS = ("embed", "attention", "moe", "loss", "block")
SORT_KERNELS = {"cvmm_fused_w1", "cvmm_fused_w2", "cvmm_dw_streamed",
                "cvmm_fwd"}
XL_REL_KERNELS = {"xl_rel_bd", "xl_rel_bd_bwd"}
KERNELS = SORT_KERNELS | XL_REL_KERNELS | {"cvmm_dw", "cvmm_gather_rows",
                                          "flash_attention"}
_WRAPPED = re.compile(r"^(?:(?:jvp|transpose)\()+(.*?)\)*$")


def _scopes(op_name: str):
    """The components of an op name, transform wrappers taken off."""
    parts = op_name.split("/")
    return [m.group(1) if m else p
            for p, m in zip(parts, map(_WRAPPED.match, parts))]


def _compile_step(arch: str, mesh=None, one_chip=None) -> str:
    """The compiled text of ``arch``'s train step cut to 2 layers (see the
    section header), on ``mesh`` with the training rules' shardings, as the
    launcher jits it, or on ``one_chip``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import OptimizerConfig, get_config
    from repro.models import build_model
    from repro.models.stack import init_mems
    from repro.optim import adamw_init
    from repro.runtime.steps import make_train_step
    from repro.sharding import TRAIN_RULES, mesh_context, tree_shardings

    cfg = get_config(arch).override(n_layers=2, xl_memory=128, dropout=0.0)
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn, impl="pallas_fused",
                                           expert_dropout=0.0))
    model = build_model(cfg, remat="full")
    step = make_train_step(model, OptimizerConfig(), mesh=mesh)
    state = jax.eval_shape(lambda k: (lambda p: {
        "params": p, "opt": adamw_init(p),
        "mems": init_mems(cfg, 4, model.dtype)})(model.init(k)),
        jax.random.PRNGKey(0))
    args = (state, {"tokens": jax.ShapeDtypeStruct((4, 129), jnp.int32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    if mesh is None:
        shardings = jax.tree_util.tree_map(lambda _: one_chip, args)
        out = None
    else:
        state_sh = tree_shardings(state, mesh, TRAIN_RULES)
        shardings = (state_sh, {"tokens": NamedSharding(mesh, P("data"))},
                     NamedSharding(mesh, P()))
        out = (state_sh, NamedSharding(mesh, P()))
    args = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        args, shardings)
    # The kernels lower for the chip only where the repo sees a TPU backend.
    with pytest.MonkeyPatch.context() as mp, mesh_context(mesh):
        mp.setattr(jax, "default_backend", lambda: "tpu")
        fn = jax.jit(step) if out is None else jax.jit(step, out_shardings=out)
        return fn.lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def compiled_step(one_chip):
    """op names of the compiled train step (see the section header)."""
    text = _compile_step("wt103-262m-moe", one_chip=one_chip)
    ops = re.findall(r'^\s*(?:ROOT )?%(\S+) = (.*?)metadata=\{op_name="([^"]*)"',
                     text, re.M)
    # every Mosaic call has op-name metadata, so none is missed below
    assert sum('"tpu_custom_call"' in rhs for _, rhs, _ in ops) \
        == text.count('custom_call_target="tpu_custom_call"') > 0
    return ops


def _names(ops, backward: bool, remat: bool = False):
    return [_scopes(name) for _, _, name in ops
            if ("transpose(" in name) == backward
            and ("rematted_computation" in name) == remat]


@pytest.mark.parametrize("layer", LAYERS)
def test_layer_scope_in_forward_and_backward(compiled_step, layer):
    assert any(layer in s for s in _names(compiled_step, backward=False))
    assert any(layer in s for s in _names(compiled_step, backward=True))


@pytest.mark.parametrize("layer", ["attention", "moe", "block"])
def test_layer_scope_in_recomputed_forward(compiled_step, layer):
    # full remat: the backward scan recomputes each block's forward
    assert any(layer in s for s in _names(compiled_step, backward=True,
                                          remat=True))


def test_optimizer_scope(compiled_step):
    names = _names(compiled_step, backward=False)
    opt = [s for s in names if "optimizer" in s]
    assert opt
    # the update runs once per step, outside every layer of the model
    assert not any(set(LAYERS) & set(s) for s in opt)


def test_every_pallas_call_carries_its_kernel_name(compiled_step):
    found = set()
    for inst, rhs, name in compiled_step:
        if 'custom_call_target="tpu_custom_call"' not in rhs:
            continue
        scopes = _scopes(name)
        kernel = [s for s in scopes if s in KERNELS]
        assert kernel, name
        # the kernel's name is also the HLO instruction's name; the sort
        # kernels run under moe, the shifted BD term under attention's
        # rel_shift
        assert inst.split(".")[0] == kernel[0]
        if kernel[0] in XL_REL_KERNELS:
            assert {"attention", "rel_shift"} <= set(scopes), name
        else:
            assert "moe" in scopes, name
        found.add(kernel[0])
    assert found == SORT_KERNELS | XL_REL_KERNELS


@pytest.mark.parametrize("backward,remat,kernel", [
    (False, False, "xl_rel_bd"),
    (True, True, "xl_rel_bd"),
    (True, False, "xl_rel_bd_bwd"),
], ids=["forward", "recomputed_forward", "backward"])
def test_xl_rel_bd_runs_in_every_pass(compiled_step, backward, remat, kernel):
    assert any(kernel in s and "rel_shift" in s
               for s in _names(compiled_step, backward=backward, remat=remat))


def test_rel_shift_moves_no_scores(compiled_step):
    # The scores of the cut-down step are (4, 16, 129, 257). The relative
    # shift of the XLA path pads, reshapes and slices tensors of that size
    # under rel_shift; with the kernel only (batch, queries, heads, dim) and
    # (keys, heads, dim) layouts of its operands remain there.
    scores = 4 * 16 * 129 * 257
    in_scope = 0
    for _, rhs, name in compiled_step:
        if ("rel_shift" not in _scopes(name)
                or 'custom_call_target="tpu_custom_call"' in rhs):
            continue
        in_scope += 1
        shape = re.match(r"\(?\w+\[([\d,]*)\]", rhs)
        size = 1
        for n in filter(None, shape.group(1).split(",")):
            size *= int(n)
        assert size < scores // 4, (rhs[:80], name)
    assert in_scope


@pytest.mark.parametrize("arch,shape", [
    ("wt103-262m-moe", (2, 2)),
    ("wt103-47m-moe", (1, 4)),
], ids=["wt103-262m-2x2", "wt103-47m-1x4"])
def test_train_step_compiles_on_a_mesh(v5e_2x2, arch, shape):
    # GSPMD cannot partition a Mosaic call: on a mesh of four chips the
    # step lowers only if every kernel runs per device inside a shard_map.
    # 2x2 splits batch and heads; wt103-47m's 10 heads do not split 4 ways.
    import numpy as np
    from jax.sharding import AxisType, Mesh

    mesh = Mesh(np.asarray(v5e_2x2).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    text = _compile_step(arch, mesh=mesh)
    found = {m.split(".")[0] for m in re.findall(
        r'^\s*(?:ROOT )?%(\S+) = .*custom_call_target="tpu_custom_call"',
        text, re.M)}
    assert found == SORT_KERNELS | XL_REL_KERNELS


# ---------------------------------------------------------------------------
# Moonlight-16B-A3B: the benchmark's cut, and what it shares with wt103
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moonlight_step(one_chip):
    """The compiled train step of the benchmark's Moonlight cut (dense
    layer + 5 MoE layers holding 8 of 64 experts, vocabulary 20480, published
    widths) on one 2048-token row, as chipbench/drivers/train.py jits it."""
    from repro.configs import OptimizerConfig, get_config
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.runtime.steps import make_train_step

    cfg = get_config("moonlight-16b-a3b").override(n_layers=6,
                                                   vocab_size=20480)
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn, n_experts=8,
                                           impl="pallas_fused"))
    model = build_model(cfg, remat="full")
    step = make_train_step(model, OptimizerConfig())
    state = jax.eval_shape(lambda k: (lambda p: {
        "params": p, "opt": adamw_init(p)})(model.init(k)),
        jax.random.PRNGKey(0))
    args = (state, {"tokens": jax.ShapeDtypeStruct((1, 2049), jnp.int32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(step, donate_argnums=(0,)).lower(*args).compile() \
            .as_text()
    return re.findall(r'^\s*(?:ROOT )?%(\S+) = (.*?)metadata=\{op_name="([^"]*)"',
                      text, re.M)


def test_moonlight_step_runs_the_sort_kernels(moonlight_step):
    found = {inst.split(".")[0] for inst, rhs, name in moonlight_step
             if 'custom_call_target="tpu_custom_call"' in rhs
             and "moe" in _scopes(name)}
    assert found == SORT_KERNELS


@pytest.mark.parametrize("layer,scope", [("attention", "mla_latent"),
                                         ("moe", "router")])
def test_moonlight_scopes_inside_their_layers(moonlight_step, layer, scope):
    # forward and backward both, each named inside its layer
    for backward in (False, True):
        names = _names(moonlight_step, backward=backward)
        inside = [s for s in names if scope in s]
        assert inside
        assert all(layer in s and s.index(layer) < s.index(scope)
                   for s in inside)


def test_wt103_step_lowers_to_the_same_ops_for_the_chip(v5e_2x2):
    """The wt103-262m-moe step through the fused kernels, lowered for one
    chip, has the ops it had before the held-share filter, selection bias
    and routed scale existed (tests/lowered_ops.py; kernel bodies aside)."""
    import json
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from lowered_ops import fingerprint, wt103_train_step_text
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "wt103_train_step_v5e.json")) as f:
        want = json.load(f)
    got = fingerprint(wt103_train_step_text(v5e_2x2[0]))
    assert got["ops"] == want["ops"]
    assert got["sha256"] == want["sha256"]
