"""Multi-device SPMD tests: run in a subprocess with 8 forced host devices so the
main pytest process keeps its single-device jax config.

Covers: logical sharding rules, sharded train step == single-device train step,
shard_map MoE EP path == einsum path, small-mesh dry-run end-to-end.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=480)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_logical_rules_basic():
    from jax.sharding import PartitionSpec as P
    import jax
    from repro.sharding.logical import spec_for_axes, TRAIN_RULES
    assert spec_for_axes(("experts", "embed", "expert_ff"), TRAIN_RULES,
                         None) == P(None, None, None)


def _tiny_meshes():
    """1-device meshes carrying the production axis names: spec resolution and
    NamedSharding's duplicate-axis validation depend only on the names, so the
    whole PARAM_AXES table can be swept in-process without forcing devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    dev = np.array(jax.devices()[:1])
    return (Mesh(dev.reshape(1, 1), ("data", "model")),
            Mesh(dev.reshape(1, 1, 1), ("pod", "data", "model")))


def test_param_axes_sweep_no_duplicate_mesh_axis():
    """Every (name, rank) in PARAM_AXES — plain, scan-stacked and doubly
    stacked — must resolve to a spec with no repeated mesh axis under every
    rule set, on both the 2-axis and the pod 3-axis mesh. Strict mode turns
    any regression into a DuplicateMeshAxisError naming the leaf (the seed
    keys_a/keys_b crash and the shared_w* entries were exactly this)."""
    import jax
    from jax.sharding import NamedSharding
    from repro.sharding import strict_duplicate_check
    from repro.sharding.logical import (PARAM_AXES, TRAIN_RULES, SERVE_RULES,
                                        spec_for_axes)
    from repro.sharding import logical as L
    rule_sets = [TRAIN_RULES, SERVE_RULES]
    if hasattr(L, "SP_RULES"):
        rule_sets.append(L.SP_RULES)
    n = 0
    with strict_duplicate_check():
        for (name, rank), axes in PARAM_AXES.items():
            for stack in ((), ("layers",), ("layers", "layers")):
                for rules in rule_sets:
                    for mesh in _tiny_meshes():
                        spec = spec_for_axes(stack + tuple(axes), rules, mesh,
                                             path=f"{name}/{rank}")
                        NamedSharding(mesh, spec)  # would also reject repeats
                        n += 1
    assert n >= 2 * len(PARAM_AXES)


def test_duplicate_resolution_first_wins_and_strict_raises():
    import jax
    import pytest as _pytest
    from jax.sharding import PartitionSpec as P
    from repro.sharding import (DuplicateMeshAxisError, spec_for_axes,
                                strict_duplicate_check)
    from repro.sharding.logical import TRAIN_RULES
    mesh2, _ = _tiny_meshes()
    bad = dict(TRAIN_RULES, oops="model")
    # default: first occurrence keeps the mesh axis, the repeat drops to None
    assert (spec_for_axes(("ffn", "embed", "oops"), bad, mesh2)
            == P("model", "data", None))
    # tuple rules drop only the repeated member
    bad2 = dict(TRAIN_RULES, fused=("data", "model"))
    assert (spec_for_axes(("ffn", "fused"), bad2, mesh2)
            == P("model", ("data",)))
    # strict mode raises, naming the leaf path and both logical axes
    with _pytest.raises(DuplicateMeshAxisError, match=r"my_leaf.*ffn.*oops"):
        with strict_duplicate_check():
            spec_for_axes(("ffn", "embed", "oops"), bad, mesh2, path="my_leaf")
    # and can be re-disabled in a nested scope
    with strict_duplicate_check():
        with strict_duplicate_check(False):
            spec_for_axes(("ffn", "embed", "oops"), bad, mesh2, path="my_leaf")


def test_pkm_key_tables_shard_on_keys_not_heads():
    """The seed bug: keys_a/keys_b ruled both 'heads' and 'pkm_keys' onto
    'model'. The fixed table keeps heads local and shards the key dim."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.logical import PARAM_AXES, TRAIN_RULES, spec_for_axes
    mesh2, _ = _tiny_meshes()
    for name in ("keys_a", "keys_b"):
        axes = PARAM_AXES[(name, 3)]
        assert spec_for_axes(axes, TRAIN_RULES, mesh2) == P(None, "data", "model")
        # scan-stacked (rank 4) and doubly stacked (rank 5)
        assert (spec_for_axes(("layers",) + tuple(axes), TRAIN_RULES, mesh2)
                == P(None, None, "data", "model"))


def test_pod_err_leaves_get_pod_axis():
    """Error-feedback state stacked per pod ((pod,)+shape leaves under 'err')
    must shard its leading dim over the 'pod' mesh axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.sharding import tree_shardings
    from repro.sharding.logical import TRAIN_RULES
    _, mesh3 = _tiny_meshes()
    tree = {"params": {"blk": {"we1": jnp.zeros((4, 8, 16))}},
            "err": {"blk": {"we1": jnp.zeros((2, 4, 8, 16)),
                            "wo": jnp.zeros((1,))}}}
    sh = tree_shardings(tree, mesh3, TRAIN_RULES)
    assert sh["err"]["blk"]["we1"].spec[0] == "pod"
    assert sh["err"]["blk"]["wo"].spec == P(None)


def test_make_local_mesh_rejects_non_divisor():
    """make_local_mesh must never silently drop devices (n=1 in-process:
    model=2 cannot divide it). The 8-device divisor sweep is in the slow
    subprocess test below."""
    import pytest as _pytest
    from repro.launch.mesh import make_local_mesh
    m = make_local_mesh()                      # model=1 always divides
    assert m.axis_names == ("data", "model")
    with _pytest.raises(ValueError, match="divis"):
        make_local_mesh(model=2)


def test_compress_pod_grads_error_feedback():
    """int8 pod-path compression: expert leaves are quantized per pod with
    error feedback (residual carried, mean over pods is the DCN reduction);
    dense leaves pass through as the exact mean."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.optim import compress_pod_grads, init_compression_state
    params = {"blk": {"we1": jnp.ones((4, 8, 16)), "wo": jnp.ones((8, 8))}}
    err = init_compression_state(params, pod=2)
    assert err["blk"]["we1"].shape == (2, 4, 8, 16)
    assert err["blk"]["wo"].shape == (1,)

    k = jax.random.PRNGKey(0)
    g = {"blk": {"we1": jax.random.normal(k, (2, 4, 8, 16)),
                 "wo": jax.random.normal(k, (2, 8, 8))}}
    exact = jnp.mean(g["blk"]["we1"], 0)
    out, err = compress_pod_grads(g, err, "int8")
    np.testing.assert_allclose(np.asarray(out["blk"]["wo"]),
                               np.asarray(jnp.mean(g["blk"]["wo"], 0)),
                               rtol=1e-6)
    one_shot = float(jnp.max(jnp.abs(out["blk"]["we1"] - exact)))
    assert one_shot > 0  # int8 actually quantizes
    # same gradient repeatedly: error feedback drives the running mean of the
    # decompressed wire values toward the exact mean
    acc, steps = out["blk"]["we1"], 8
    for _ in range(steps - 1):
        out, err = compress_pod_grads(g, err, "int8")
        acc = acc + out["blk"]["we1"]
    avg_err = float(jnp.max(jnp.abs(acc / steps - exact)))
    assert avg_err < one_shot / 2


@pytest.mark.slow
def test_sharded_train_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import reduced
    from repro.configs.base import OptimizerConfig
    from repro.models import build_model
    from repro.runtime.steps import init_train_state, make_train_step
    from repro.sharding import TRAIN_RULES, mesh_context, tree_shardings

    cfg = reduced("llama3-8b")
    model = build_model(cfg)
    opt = OptimizerConfig(lr=1e-3)
    step = make_train_step(model, opt)
    key = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                          cfg.vocab_size)}
    rng = jax.random.PRNGKey(2)

    # single device
    state1 = init_train_state(model, key, opt)
    s1, m1 = jax.jit(step)(state1, batch, rng)

    # 4x2 mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    with mesh_context(mesh):
        state2 = init_train_state(model, key, opt)
        state2 = jax.device_put(state2, tree_shardings(state2, mesh, TRAIN_RULES))
        s2, m2 = jax.jit(step)(state2, batch, rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-4)
    l1 = jax.tree_util.tree_leaves(s1["params"])
    l2 = jax.tree_util.tree_leaves(s2["params"])
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-3)
    print("SHARDED==SINGLE OK")
    """)


@pytest.mark.slow
def test_shard_map_moe_matches_einsum():
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import moe_ffn
    from repro.core import apply_moe, init_moe
    from repro.sharding import mesh_context, tree_shardings, TRAIN_RULES
    from jax.sharding import NamedSharding, PartitionSpec as P

    d, ne, g, k = 32, 8, 16, 2
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg_e = moe_ffn(ne, g, k, dispatch="einsum", capacity_factor=8.0)
    cfg_s = dataclasses.replace(cfg_e, dispatch="shard_map")
    p = init_moe(jax.random.PRNGKey(1), d, cfg_e, n_layers=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, d))
    with mesh_context(mesh):
        pp = jax.device_put(p, tree_shardings(p, mesh, TRAIN_RULES))
        xx = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        ye, _ = jax.jit(lambda p, x: apply_moe(p, x, cfg_e))(pp, xx)
        ys, _ = jax.jit(lambda p, x: apply_moe(p, x, cfg_s))(pp, xx)
        # gradients through the shard_map path
        gs = jax.jit(jax.grad(lambda p, x: apply_moe(p, x, cfg_s)[0].sum()))(pp, xx)
        ge = jax.jit(jax.grad(lambda p, x: apply_moe(p, x, cfg_e)[0].sum()))(pp, xx)
    np.testing.assert_allclose(np.asarray(ye), np.asarray(ys), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ge), jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    print("SHARD_MAP==EINSUM OK")
    """)


@pytest.mark.slow
def test_pkm_state_shards_on_real_mesh():
    """The seed acceptance bug end-to-end: a real --ffn pkm train state must
    produce valid NamedShardings (strict duplicate checking on) under a
    (data=4, model=2) mesh, and make_local_mesh must reject non-divisors /
    build the 3-axis pod mesh."""
    _run("""
    import jax
    from repro.configs import reduced
    from repro.configs.base import OptimizerConfig
    from repro.models import build_model
    from repro.runtime.steps import init_train_state
    from repro.launch.mesh import make_local_mesh
    from repro.sharding import (TRAIN_RULES, mesh_context, tree_shardings,
                                strict_duplicate_check)

    # mesh construction contract on 8 devices
    m = make_local_mesh(model=2)
    assert dict(zip(m.axis_names, m.devices.shape)) == {"data": 4, "model": 2}
    m3 = make_local_mesh(model=2, pod=2)
    assert dict(zip(m3.axis_names, m3.devices.shape)) == {
        "pod": 2, "data": 2, "model": 2}
    try:
        make_local_mesh(model=3)
        raise SystemExit("model=3 on 8 devices must raise")
    except ValueError as e:
        assert "divis" in str(e)

    cfg = reduced("wt103-47m-moe").override(xl_memory=0)
    model = build_model(cfg, ffn="pkm")
    state = jax.eval_shape(
        lambda k: init_train_state(model, k, OptimizerConfig()),
        jax.random.PRNGKey(0))
    for mesh in (m, m3):
        with mesh_context(mesh), strict_duplicate_check():
            sh = tree_shardings(state, mesh, TRAIN_RULES)
            for s in jax.tree_util.tree_leaves(sh):
                pass  # NamedSharding construction inside tree_shardings
    print("PKM STATE SHARDS OK")
    """)


@pytest.mark.slow
def test_shard_map_ep_matches_sort_oracle():
    """EP shard_map dispatch == the dropless sort-path oracle, forward and
    backward, on an 8-device (data, model) mesh. capacity_factor is high so
    nothing is dropped and the two paths compute the same function; the EP
    local FFN runs through the planned-CVMM machinery (ep_plan_stats must
    report a coherent plan for the same shapes)."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import moe_ffn
    from repro.core import apply_moe, init_moe
    from repro.core.dispatch import ep_plan_stats
    from repro.sharding import mesh_context, tree_shardings, TRAIN_RULES
    from jax.sharding import NamedSharding, PartitionSpec as P

    d, ne, g, k, n = 32, 8, 16, 2, 64
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg_o = moe_ffn(ne, g, k, dispatch="sort", capacity_factor=8.0)
    cfg_s = dataclasses.replace(cfg_o, dispatch="shard_map")
    p = init_moe(jax.random.PRNGKey(1), d, cfg_o, n_layers=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    with mesh_context(mesh):
        pp = jax.device_put(p, tree_shardings(p, mesh, TRAIN_RULES))
        xx = jax.device_put(x, NamedSharding(mesh, P(("data", "model"), None)))
        yo, _ = jax.jit(lambda p, x: apply_moe(p, x, cfg_o))(pp, xx)
        ys, aux = jax.jit(lambda p, x: apply_moe(p, x, cfg_s))(pp, xx)
        assert float(aux["moe_dropped"]) == 0.0, aux
        go = jax.jit(jax.grad(lambda p, x: apply_moe(p, x, cfg_o)[0].sum()))(pp, xx)
        gs = jax.jit(jax.grad(lambda p, x: apply_moe(p, x, cfg_s)[0].sum()))(pp, xx)
        stats = ep_plan_stats(cfg_s, n, ne, mesh)
        assert stats["e_local"] == ne // 4
        assert stats["rows_per_shard"] == stats["e_local"] * stats["capacity"] * 4
        assert stats["run_batched"] > 0
        # the EP capacity buffer is fully contiguous: whole tiles pack into
        # few descriptors, so batching must beat one-DMA-per-row
        assert stats["batching_factor"] > 1.0, stats
    np.testing.assert_allclose(np.asarray(yo), np.asarray(ys), atol=1e-5)
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(go),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(gs),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=str(ka))
    print("EP==SORT OK")
    """)


@pytest.mark.slow
def test_pod_tier_compressed_convergence():
    """Compressed-gradient convergence smoke on a (pod=2, data=2, model=2)
    mesh: the pod-tier int8 error-feedback path must track the exact-gradient
    run (loss and parameter divergence within tolerance over N steps), and the
    error state must be pod-stacked and pod-sharded."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced
    from repro.configs.base import OptimizerConfig
    from repro.models import build_model
    from repro.runtime.steps import init_train_state, make_train_step
    from repro.launch.mesh import make_local_mesh
    from repro.sharding import TRAIN_RULES, mesh_context, tree_shardings

    cfg = reduced("wt103-47m-moe").override(xl_memory=0)
    model = build_model(cfg, ffn="sigma_moe")
    cfg = model.cfg
    mesh = make_local_mesh(model=2, pod=2)
    steps, bsz, seq = 8, 8, 16
    key = jax.random.PRNGKey(0)

    def train(compression):
        opt = OptimizerConfig(lr=1e-3, total_steps=steps,
                              grad_compression=compression)
        with mesh_context(mesh):
            state = init_train_state(model, key, opt, pod=2)
            state = jax.device_put(state,
                                   tree_shardings(state, mesh, TRAIN_RULES))
            step = jax.jit(make_train_step(model, opt, mesh=mesh))
            losses = []
            for s in range(steps):
                tokens = jax.random.randint(jax.random.fold_in(key, 100 + s),
                                            (bsz, seq + 1), 0, cfg.vocab_size)
                state, m = step(state, {"tokens": tokens},
                                jax.random.PRNGKey(7))
                losses.append(float(m["loss"]))
            return losses, state

    l_exact, s_exact = train("none")
    l_int8, s_int8 = train("int8")

    # err leaves for expert params are pod-stacked (leading dim 2)
    from repro.optim import is_expert_leaf
    flat = jax.tree_util.tree_flatten_with_path(s_int8["err"])[0]
    n_pod = 0
    for path, leaf in flat:
        if is_expert_leaf(path):
            assert leaf.shape[0] == 2, (path, leaf.shape)
            n_pod += 1
        else:
            assert leaf.shape == (1,), (path, leaf.shape)
    assert n_pod > 0

    # convergence: compressed run tracks the exact run
    for le, li in zip(l_exact, l_int8):
        assert abs(le - li) < 0.05, (l_exact, l_int8)
    pe = jax.tree_util.tree_leaves(s_exact["params"])
    pi = jax.tree_util.tree_leaves(s_int8["params"])
    rel = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                    b.astype(jnp.float32))))
              for a, b in zip(pe, pi))
    assert rel < 5e-2, rel
    print("POD COMPRESSION CONVERGENCE OK", l_exact[-1], l_int8[-1])
    """)


@pytest.mark.slow
def test_small_mesh_dryrun_all_modes():
    """End-to-end mini dry-run: 4x2 mesh, one arch, train+prefill+decode lower and
    compile; roofline report extracted."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.configs import reduced, SHAPES, ShapeConfig
    from repro.configs.base import OptimizerConfig
    from repro.models import build_model
    from repro.roofline import analyze_compiled
    from repro.runtime.steps import init_train_state, make_train_step
    from repro.sharding import TRAIN_RULES, SERVE_RULES, mesh_context, tree_shardings

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = reduced("granite-moe-3b-a800m")
    model = build_model(cfg, remat="full", ep_degree=2)
    shp = ShapeConfig("mini_train", 64, 8, "train")

    with mesh_context(mesh):
        def sds(tree, rules):
            sh = tree_shardings(tree, mesh, rules)
            return jax.tree_util.tree_map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                tree, sh)
        inputs = sds(model.input_specs(shp), TRAIN_RULES)
        state = sds(jax.eval_shape(
            lambda k: init_train_state(model, k, OptimizerConfig()),
            jax.random.PRNGKey(0)), TRAIN_RULES)
        step = make_train_step(model, OptimizerConfig())
        comp = jax.jit(step).lower(state, inputs,
                                   jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        rep = analyze_compiled(comp, arch="granite-mini", shape=shp,
                               mesh_name="4x2", n_chips=8, cfg=cfg)
        assert rep.flops > 0 and rep.hbm_bytes > 0
        assert comp.memory_analysis() is not None

        # decode
        params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)), SERVE_RULES)
        cache = sds(jax.eval_shape(lambda: model.init_cache(8, 64)), SERVE_RULES)
        tok = jax.ShapeDtypeStruct((8,), jnp.int32)
        dcomp = jax.jit(model.decode_step).lower(
            params, cache, tok, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        assert dcomp.memory_analysis() is not None
    print("MINI DRYRUN OK")
    """)
