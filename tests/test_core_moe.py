"""sigma-MoE and baselines: routing, dispatch-path equivalence, regularizers,
initialization, expert dropout (paper Secs. 3.3-5)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import moe_ffn
from repro.core import (apply_moe, entropy_reg, init_moe, norm_topk,
                        select_experts, sinkhorn, usage_stats)
from repro.core.routing import SelectionInfo

D, NE, G, K = 32, 8, 16, 2


def _setup(dispatch="sort", **kw):
    cfg = moe_ffn(NE, G, K, dispatch=dispatch, **kw)
    p = init_moe(jax.random.PRNGKey(1), D, cfg, n_layers=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 10, D))
    return cfg, p, x


def test_sort_equals_einsum_without_drops():
    cfg_s, p, x = _setup("sort")
    cfg_e = dataclasses.replace(cfg_s, dispatch="einsum", capacity_factor=16.0)
    ys, _ = apply_moe(p, x, cfg_s)
    ye, _ = apply_moe(p, x, cfg_e)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ye), atol=1e-5, rtol=1e-5)


def test_moe_equals_dense_when_all_experts_selected():
    """K = N_E with gates forced to 1 must reproduce the dense MLP y = W2 relu(W1 x):
    the unified-view consistency check (paper Sec. 3)."""
    cfg, p, x = _setup("sort")
    cfg = dataclasses.replace(cfg, k=NE)
    # zero router -> sigmoid(0) = 0.5 for every expert -> y == 0.5 * dense MLP
    p = dict(p, router=jnp.zeros_like(p["router"]))
    y, _ = apply_moe(p, x, cfg)
    w1 = np.concatenate([np.asarray(p["we1"][e]) for e in range(NE)], axis=1)
    w2 = np.concatenate([np.asarray(p["we2"][e]) for e in range(NE)], axis=0)
    dense = np.maximum(np.asarray(x) @ w1, 0) @ w2
    np.testing.assert_allclose(2.0 * np.asarray(y), dense, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kind,act", [("sigma_moe", "sigmoid"),
                                      ("switch", "softmax"),
                                      ("noisy_topk", "softmax"),
                                      ("sbase", "sigmoid")])
@pytest.mark.parametrize("dispatch", ["sort", "einsum"])
def test_variants_forward_backward(kind, act, dispatch):
    cfg, p, x = _setup(dispatch, selector_activation=act, reg_kind="entropy",
                       reg_gamma=0.01)
    cfg = dataclasses.replace(cfg, kind=kind, expert_dropout=0.1)
    p = init_moe(jax.random.PRNGKey(1), D, cfg, n_layers=4)
    y, aux = apply_moe(p, x, cfg, rng=jax.random.PRNGKey(2), train=True)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    g = jax.grad(lambda p: apply_moe(p, x, cfg, rng=jax.random.PRNGKey(2),
                                     train=True)[0].sum())(p)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_expert_dropout_masks_whole_experts():
    cfg, p, x = _setup("sort")
    cfg = dataclasses.replace(cfg, expert_dropout=0.9)
    # with delta=0.9 nearly all experts are dropped -> selected set shrinks
    infos = []
    xf = x.reshape(-1, D)
    logits = xf @ p["router"]
    i_train = select_experts(logits, dataclasses.replace(cfg, expert_dropout=0.9),
                             rng=jax.random.PRNGKey(3), train=True)
    i_eval = select_experts(logits, cfg, train=False)
    # eval ignores dropout: top-k gates strictly positive
    assert np.all(np.asarray(i_eval.gates) > 0)
    # train: dropped experts produce zero gates for at least some tokens
    assert np.asarray(i_train.gates).min() == 0.0


def test_sigma_init_matches_dense_std():
    cfg, p, _ = _setup("sort")
    import math
    s1 = math.sqrt(2.0 / (D * 4))
    s2 = math.sqrt(2.0 / (NE * G * 4))
    assert abs(np.asarray(p["we1"]).std() - s1) / s1 < 0.1
    assert abs(np.asarray(p["we2"]).std() - s2) / s2 < 0.1
    # router rows all have equal norm (footnote 5)
    norms = np.linalg.norm(np.asarray(p["router"]), axis=0)
    np.testing.assert_allclose(norms, norms[0], rtol=1e-5)


def test_standard_init_differs():
    cfg = moe_ffn(NE, G, K, sigma_moe_init=False)
    p = init_moe(jax.random.PRNGKey(1), D, cfg, n_layers=4)
    assert abs(np.asarray(p["we2"]).std() - (0.1 / G) ** 0.5) < 0.02


def test_entropy_reg_minimized_by_uniform():
    probs_uniform = jnp.full((64, NE), 1.0 / NE)
    probs_peaky = jnp.zeros((64, NE)).at[:, 0].set(1.0)
    mk = lambda pr: SelectionInfo(probs=pr, sel=pr,
                                  idx=jnp.zeros((64, K), jnp.int32),
                                  gates=jnp.ones((64, K)))
    assert entropy_reg(mk(probs_uniform), NE) < entropy_reg(mk(probs_peaky), NE)


def test_sinkhorn_balances_columns():
    logits = jax.random.normal(jax.random.PRNGKey(0), (128, NE)) * 3.0
    pi = sinkhorn(logits, 20)
    col = np.asarray(pi.sum(0))
    np.testing.assert_allclose(col, 128 / NE, rtol=0.05)
    row = np.asarray(pi.sum(1))
    np.testing.assert_allclose(row, 1.0, rtol=0.05)


def test_norm_topk_sums_to_one():
    s = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (32, NE)))
    gates, idx = norm_topk(s, K)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)


def test_padded_experts_never_selected():
    cfg = moe_ffn(6, G, K)               # 6 experts, pad to 8 (ep_degree=4 -> 8)
    p = init_moe(jax.random.PRNGKey(1), D, cfg, n_layers=2, ep_degree=4)
    assert p["we1"].shape[0] == 8
    x = jax.random.normal(jax.random.PRNGKey(0), (16, D))
    xf = x.reshape(-1, D)
    logits = xf @ p["router"]
    logits = jnp.concatenate([logits, jnp.full((16, 2), -1e9)], -1)
    info = select_experts(logits, cfg, train=False, n_valid_experts=6)
    assert np.asarray(info.idx).max() < 6


def test_capacity_drops_reported():
    cfg, p, x = _setup("einsum", capacity_factor=0.25)
    y, aux = apply_moe(p, x, cfg)
    assert float(aux["moe_dropped"]) > 0.0


def test_usage_stats_detects_collapse():
    idx_collapsed = jnp.zeros((128, K), jnp.int32)
    idx_uniform = jnp.stack([jnp.arange(128) % NE,
                             (jnp.arange(128) + 1) % NE], -1)
    gates = jnp.ones((128, K))
    probs = jnp.full((128, NE), 1.0 / NE)
    s_c = usage_stats(SelectionInfo(probs, probs, idx_collapsed, gates), NE)
    s_u = usage_stats(SelectionInfo(probs, probs, idx_uniform, gates), NE)
    assert float(s_c["usage_entropy"]) < float(s_u["usage_entropy"])


@pytest.mark.parametrize("glu", [False, True])
def test_shard_map_parity_and_no_dummy_glu_weight(glu, monkeypatch):
    """shard_map EP path == einsum path for GLU on AND off, on a real (single
    device) 'model' mesh so the shard_map branch actually runs (it lives in
    core/dispatch.py — the shared execution layer — since the PR 5 refactor).
    Guards the dummy-w1g fix: the non-GLU path must ship exactly 5 operands
    through shard_map (no (E,1,1) zeros placeholder, no size-1-broadcast
    einsum)."""
    from repro.core import dispatch as dispatch_mod
    from repro.launch.mesh import make_mesh
    from repro.sharding import mesh_context

    cfg_e = moe_ffn(NE, G, K, dispatch="einsum", capacity_factor=8.0)
    cfg_e = dataclasses.replace(cfg_e, glu_experts=glu)
    cfg_s = dataclasses.replace(cfg_e, dispatch="shard_map")
    p = init_moe(jax.random.PRNGKey(1), D, cfg_e, n_layers=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, D))

    shipped = {}
    orig = dispatch_mod._shard_map

    def spy(fn, **kw):
        inner = orig(fn, **kw)

        def call(*args):
            shipped["n_operands"] = len(args)
            return inner(*args)
        return call

    monkeypatch.setattr(dispatch_mod, "_shard_map", spy)
    mesh = make_mesh((1,), ("model",))
    with mesh_context(mesh):
        ye, _ = apply_moe(p, x, cfg_e)
        ys, _ = apply_moe(p, x, cfg_s)
        gs = jax.grad(lambda p: apply_moe(p, x, cfg_s)[0].sum())(p)
        ge = jax.grad(lambda p: apply_moe(p, x, cfg_e)[0].sum())(p)
    assert shipped["n_operands"] == (6 if glu else 5)
    np.testing.assert_allclose(np.asarray(ye), np.asarray(ys), atol=1e-5)
    for name in ge:
        np.testing.assert_allclose(np.asarray(ge[name]), np.asarray(gs[name]),
                                   atol=1e-4, err_msg=name)


def test_sort_dispatch_falls_back_to_ragged_when_no_tile_fits(monkeypatch):
    """_pick_tn returning None must not crash the sort path: when even the
    UNFUSED pallas kernels cannot tile the working set into VMEM,
    dispatch._sort_path falls back to XLA's ragged grouped matmul instead of
    raising at trace time (and stays numerically identical to an explicit
    ragged run)."""
    from repro.kernels import cvmm, ops as kops

    cfg, p, x = _setup("sort")
    # d=32 -> k_pad=128: tn=128 needs > 128KiB; starve it so nothing fits.
    monkeypatch.setattr(cvmm, "VMEM_BUDGET", 1 << 16)
    assert not kops.pallas_supported(D, cfg.expert_size)
    assert not kops.fused_supported(40, D, cfg.expert_size, cfg.activation)
    kops.set_default_impl("pallas_fused_interpret")
    try:
        y, _ = apply_moe(p, x, cfg)
        gy = jax.grad(lambda p: apply_moe(p, x, cfg)[0].sum())(p)
    finally:
        kops.set_default_impl(None)
    kops.set_default_impl("ragged")
    try:
        yr, _ = apply_moe(p, x, cfg)
        gr = jax.grad(lambda p: apply_moe(p, x, cfg)[0].sum())(p)
    finally:
        kops.set_default_impl(None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-6)
    for name in gr:
        np.testing.assert_allclose(np.asarray(gy[name]), np.asarray(gr[name]),
                                   atol=1e-5, err_msg=name)
