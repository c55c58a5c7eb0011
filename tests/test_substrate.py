"""Substrate tests: optimizer, schedules, data determinism, checkpoint atomicity +
resharding, gradient compression, chunked CE."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs.base import OptimizerConfig
from repro.data import DataIterator, make_dataset
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         compress_grads, init_compression_state, make_schedule)
from repro.runtime.loss import chunked_cross_entropy


def test_adamw_decreases_quadratic():
    cfg = OptimizerConfig(lr=0.1, schedule="constant", grad_clip=1e9)
    params = {"w": jnp.array([3.0, -2.0])}
    opt = adamw_init(params)
    for _ in range(200):
        g = {"w": 2 * params["w"]}
        params, opt = adamw_update(g, opt, params, cfg, jnp.float32(0.05))
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_schedules():
    for kind in ("cosine", "wsd", "constant"):
        cfg = OptimizerConfig(lr=1e-3, schedule=kind, warmup_steps=10,
                              total_steps=100)
        s = make_schedule(cfg)
        assert float(s(jnp.int32(0))) == 0.0 or kind == "constant"
        assert abs(float(s(jnp.int32(10))) - 1e-3) < 1e-9
        if kind == "cosine":
            assert float(s(jnp.int32(100))) < 1e-5
        if kind == "wsd":
            assert abs(float(s(jnp.int32(50))) - 1e-3) < 1e-9   # stable phase
            assert float(s(jnp.int32(100))) < 1e-4              # decayed


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-5


def test_grad_compression_error_feedback():
    """Error feedback: sum of decompressed grads converges to sum of true grads."""
    g_true = jnp.array([1e-3, 2.5e-4, -3.33e-4, 0.1])
    err = init_compression_state({"g": g_true})
    total = jnp.zeros(4)
    for i in range(50):
        wire, err = compress_grads({"g": g_true}, err, "int8")
        total = total + wire["g"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(g_true) * 50,
                               rtol=0.02, atol=1e-4)


def test_data_determinism_and_resume():
    ds = make_dataset("synthetic", 256)
    a = DataIterator(ds, 8, 32, seed=1)
    b = DataIterator(ds, 8, 32, seed=1)
    for _ in range(3):
        a.next()
    state = a.state()
    b.restore(state)
    np.testing.assert_array_equal(a.next()["tokens"], b.next()["tokens"])


def test_data_host_sharding_partitions_global_batch():
    ds = make_dataset("synthetic", 256)
    full = DataIterator(ds, 8, 16, seed=2)
    h0 = DataIterator(ds, 8, 16, seed=2, host_index=0, host_count=2)
    h1 = DataIterator(ds, 8, 16, seed=2, host_index=1, host_count=2)
    f = full.next()["tokens"]
    np.testing.assert_array_equal(f[:4], h0.next()["tokens"])
    np.testing.assert_array_equal(f[4:], h1.next()["tokens"])


def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    for step in (1, 2, 3):
        mgr.save(step, tree, extra={"data": {"step": step}})
    assert mgr.all_steps() == [2, 3]               # keep=2 garbage-collected
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored, extra = mgr.restore(like)
    assert extra["step"] == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_atomicity(tmp_path):
    """A torn tmp dir (crash mid-save) is never visible as a checkpoint."""
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(5, {"x": jnp.ones(3)})
    os.makedirs(tmp_path / "tmp.6.999", exist_ok=True)      # simulated torn write
    (tmp_path / "tmp.6.999" / "meta.json").write_text("{corrupt")
    assert mgr.latest_step() == 5


def test_checkpoint_reshard_restore(tmp_path):
    """Elastic restore: save unsharded, restore with a different sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, tree)
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = mgr.restore(tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


@pytest.mark.parametrize("chunks", [1, 4])
def test_chunked_ce_matches_dense(chunks):
    b, s, d, v = 2, 9, 16, 50
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (b, s, d))
    w = jax.random.normal(ks[1], (d, v)) * 0.1
    labels = jax.random.randint(ks[2], (b, s), 0, v)
    dense, _ = chunked_cross_entropy(h, w, labels, chunks=1)
    ck, _ = chunked_cross_entropy(h, w, labels, chunks=chunks)
    np.testing.assert_allclose(float(dense), float(ck), rtol=1e-5)
    # grads too
    gd = jax.grad(lambda h: chunked_cross_entropy(h, w, labels, chunks=1)[0])(h)
    gc = jax.grad(lambda h: chunked_cross_entropy(h, w, labels, chunks=chunks)[0])(h)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gc), atol=1e-5)


def test_chunked_ce_vocab_mask():
    b, s, d, v = 1, 4, 8, 32
    h = jax.random.normal(jax.random.PRNGKey(0), (b, s, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v)) * 0.1
    labels = jnp.zeros((b, s), jnp.int32)
    full, _ = chunked_cross_entropy(h, w, labels)
    masked, _ = chunked_cross_entropy(h, w, labels, n_valid_vocab=16)
    # masking vocab reduces the partition function -> lower or equal CE
    assert float(masked) <= float(full) + 1e-6
