"""Moonlight-16B-A3B (the DeepSeek-V3 block) against its plain float32
reference (chipbench/configs/moonlight-16b-a3b-ep8.ref.py) at a small size:
MLA and the flash core with a value width of its own, a layer that holds a
share of the experts, the selection bias and the sequence-wise balance loss,
and the whole train step. Programs run in float32 here, so the tolerances
are round-off of float32 sums (the chip's bfloat16 is judged by the
benchmark's own check)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, get_config, reduced
from repro.core.moe import apply_moe, init_moe, update_selection_bias
from repro.models import build_model
from repro.models.attention import apply_attention, flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "moonlight-16b-a3b"
# float32 sums of a few hundred terms, in another order than the reference's
F32 = dict(rtol=2e-5, atol=2e-6)


@pytest.fixture(scope="module")
def ref():
    from chipbench.harness import load_module
    return load_module(os.path.join(ROOT, "chipbench", "configs",
                                    "moonlight-16b-a3b-ep8.ref.py"))


def _cfg(held=16, offset=0):
    cfg = reduced(ARCH).override(dtype="float32")
    return cfg.with_ffn(dataclasses.replace(cfg.ffn, n_experts=held,
                                            expert_offset=offset))


def _sizes(cfg):
    a, f = cfg.attention, cfg.ffn
    m = {"n_heads": a.n_heads, "head_dim": a.head_dim, "norm_eps": cfg.norm_eps,
         "rope_theta": a.rope_theta, "n_experts": f.n_experts, "k": f.k}
    s = {"kv_lora_rank": a.kv_lora_rank, "rope_head_dim": a.rope_head_dim,
         "v_head_dim": a.v_head_dim, "router": f.n_router,
         "routed_scale": f.routed_scale}
    return m, s


def _plain_mm(spec, a, b):
    return jnp.einsum(spec, a, b)


def test_flash_attention_value_width_of_its_own():
    """q.k at 24, values of 16: the chunked online softmax against a plain
    masked softmax."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (2, 50, 4, 24))
    k = jax.random.normal(kk, (2, 50, 4, 24))
    v = jax.random.normal(kv, (2, 50, 4, 16))
    out = flash_attention(q, k, v, causal=True, scale=24 ** -0.5, kv_chunk=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 24 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((50, 50), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    assert out.shape == (2, 50, 4, 16)
    np.testing.assert_allclose(out, want, **F32)


def test_mla_matches_reference(ref):
    cfg = _cfg()
    m, s = _sizes(cfg)
    model = build_model(cfg)
    p = model.init(jax.random.PRNGKey(1))["stack"]["segments"][1]["e0"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)["attn"]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 37, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got, _ = apply_attention(p, h, cfg)
        want = ref.mla(p, h, m, s, _plain_mm)
    np.testing.assert_allclose(got, want, **F32)


def _share(params, lo, n):
    """A layer's parameters as the chip holding experts [lo, lo + n) has
    them: its routed experts, and the router, bias and shared experts
    whole."""
    return {k: (v[lo:lo + n] if k in ("we1", "we1g", "we2") else v)
            for k, v in params.items()}


@pytest.mark.parametrize("impl", ["ragged", "pallas_fused_interpret"])
def test_shares_add_up(ref, impl):
    """Four chips holding 4 of 16 routed experts each: their partial outputs,
    with the shared experts (which every chip computes) counted once, add
    up to the uncut reference layer."""
    cfg = _cfg()
    f = dataclasses.replace(cfg.ffn, impl=impl)
    d = cfg.d_model
    params = init_moe(jax.random.PRNGKey(3), d, f, cfg.n_layers)
    params["router_bias"]["bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(4), (f.n_router,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, d))
    with jax.default_matmul_precision("highest"):
        parts = []
        for lo in (0, 4, 8, 12):
            fs = dataclasses.replace(f, n_experts=4, expert_offset=lo)
            y, aux = apply_moe(_share(params, lo, 4), x, fs)
            parts.append(y)
        xf = x.reshape(-1, d)
        y_shared = sum(ref.glu(xf, params["shared_w1"][j],
                              params["shared_w1g"][j], params["shared_w2"][j],
                              _plain_mm) for j in range(f.n_shared_experts))
        m, s = _sizes(cfg)
        want, _, _ = ref.moe(params, x, m, s, _plain_mm)
    got = sum(parts) - (len(parts) - 1) * y_shared.reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # every routed row lands in exactly one share
    assert float(aux["moe_rows_held"]) > 0
    # the last share's sort plan: a tile for each held expert with rows,
    # of ceil(48 * k / 128) + 4 tiles
    load = np.asarray(aux["load"])[12:16]
    assert float(aux["moe_tiles_live"]) == np.sum(np.ceil(load / 128))
    assert float(aux["moe_tiles"]) == -(-x.shape[0] * x.shape[1] * f.k
                                        // 128) + 4


def test_balance_loss_and_loads_match_reference(ref):
    cfg = _cfg(held=8)
    f = cfg.ffn
    params = init_moe(jax.random.PRNGKey(6), cfg.d_model, f, cfg.n_layers)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 20, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        _, aux = apply_moe(params, x, f)
        m, s = _sizes(cfg)
        _, balance, load = ref.moe(params, x, m, s, _plain_mm)
    np.testing.assert_allclose(aux["moe_reg"], f.reg_gamma * balance, **F32)
    np.testing.assert_array_equal(aux["load"], load)
    # the held rows: the loads of experts [0, 8)
    assert float(aux["moe_rows_held"]) == float(jnp.sum(load[:8]))


def test_selection_bias_moves_toward_the_mean_load():
    bias = jnp.zeros((4,))
    load = jnp.array([10.0, 2.0, 4.0, 0.0])      # mean 4
    out = update_selection_bias(bias, load, 1e-3)
    np.testing.assert_allclose(out, [-1e-3, 1e-3, 0.0, 1e-3])


def test_train_step_matches_reference(ref):
    """Loss, every gradient leaf, and three AdamW steps (with the selection
    bias update) of the program against ``train_reference``: the chip's
    check, at a small size in float32."""
    from chipbench.model import make_weights, stated
    from repro.optim import adamw_init
    from repro.runtime.steps import make_train_step

    cfg = reduced(ARCH).override(dtype="float32", n_layers=4)
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn, n_experts=8))
    train = {"remat": "full", "optimizer": {
        "lr": 1e-3, "total_steps": 100, "grad_clip": 1.0, "b1": 0.9,
        "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "schedule": "cosine",
        "warmup_steps": 0}}
    model = build_model(cfg, remat="full")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seed = 2 ** 33 + 9
    batches = np.asarray(jax.random.randint(
        jax.random.PRNGKey(8), (3, 2, 33), 0, cfg.vocab_size))
    m = stated(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.train_reference(m, train, shapes, batches, seed)
        params = make_weights(shapes, seed)
        # the first gradient, leaf by leaf
        g = jax.grad(lambda p: model.loss(p, {"tokens": batches[0]})[0])(params)
        s = ref.settings(m, shapes)
        g_ref = jax.grad(lambda p: ref.forward_loss(
            p, jnp.asarray(batches[0]), m, s, None)[0])(params)
        # each leaf's error over its norm: float32 round-off, summed in
        # another order (single elements near 0 differ by more, relatively)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(g_ref)):
            assert float(jnp.linalg.norm((a - b).ravel())) <= 1e-4 * float(
                jnp.linalg.norm(b.ravel())) + 1e-12
        step = jax.jit(make_train_step(model, OptimizerConfig(
            **train["optimizer"])))
        state = {"params": params, "opt": adamw_init(params)}
        losses = []
        for b in batches:
            state, met = step(state, {"tokens": jnp.asarray(b)},
                              jax.random.PRNGKey(0))
            losses.append(float(met["loss"]))
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    change = [jnp.linalg.norm((a - b).ravel()) for a, b in zip(
        jax.tree_util.tree_leaves(state["params"]),
        jax.tree_util.tree_leaves(make_weights(shapes, seed)))]
    # Adam divides each gradient by its own root mean square, so a leaf's
    # first steps move by about lr whatever its size: 1e-3 of relative
    # room covers the float32 gradients' differences above
    np.testing.assert_allclose(change, want["update_norms"], rtol=1e-3)


def test_published_sizes():
    cfg = get_config(ARCH)
    pc = cfg.param_counts()
    # hf card: 16B total, 3B activated; the card counts the input
    # embedding among the activated, param_counts leaves the lookup out
    assert 15.5e9 < pc["total"] < 16.5e9
    assert 2.8e9 < pc["active"] + cfg.vocab_size * cfg.d_model < 3.1e9
    cut = cfg.override(n_layers=6, vocab_size=20480)
    cut = cut.with_ffn(dataclasses.replace(cut.ffn, n_experts=8))
    # model-configs Sec. 4's reckoning of this very cut: 669M parameters
    assert cut.param_counts()["total"] == 668_863_488


def test_paged_serving_refuses_mla():
    lm = build_model(reduced(ARCH))
    with pytest.raises(NotImplementedError, match="latent"):
        lm.init_paged_cache(4, 8)


def test_launcher_trains_the_reduced_arch(tmp_path):
    from repro.launch.train import run
    out = run(["--arch", ARCH, "--reduced", "--steps", "3", "--batch", "2",
               "--seq", "16", "--ckpt-every", "0", "--log-every", "1",
               "--ckpt-dir", str(tmp_path)])
    assert len(out.history) == 3
    for h in out.history:
        assert np.isfinite(h["loss"])
        # 2 x 17 tokens, top-4 of 16, all held, in each of 2 MoE layers
        assert h["moe_rows_held"] == 2 * 17 * 4 * 2
        # each layer's plan: 2 + 16 tiles, one live per expert with rows
        # (at most 34 rows each, so 4 to 16 of them)
        live = h["moe_tile_share"] * 2 * 18
        assert abs(live - round(live)) < 1e-4 and 2 * 4 <= live <= 2 * 16
    bias = out.state["params"]["stack"]["segments"][1]["e0"]["ffn"][
        "router_bias"]["bias"]
    assert float(jnp.max(jnp.abs(bias))) > 0


def test_wt103_train_step_lowers_to_the_same_ops():
    """The held-share filter, selection bias, routed scale and new metrics
    are static no-ops for a layer that holds all its experts: the lowered
    wt103-262m-moe step has the ops it had before they existed (fingerprint
    recorded from that tree by tests/lowered_ops.py)."""
    import json
    from lowered_ops import fingerprint, wt103_train_step_text
    with open(os.path.join(ROOT, "tests", "data",
                           "wt103_train_step_cpu.json")) as fh:
        want = json.load(fh)
    got = fingerprint(wt103_train_step_text())
    assert got["ops"] == want["ops"]
    assert got["sha256"] == want["sha256"]
