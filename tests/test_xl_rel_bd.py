"""The shifted xl_rel BD kernel (interpret mode) against its definition and
against the XLA path it replaces on TPU (einsum + ``_rel_shift``).

Both agree on the causal-valid region ``j <= (sk - sq) + i``, where

    bd[b, h, i, j] = qv[b, i, h] . r[j + sq - 1 - i, h];

outside it the values are free (the mask overwrites them) and only have to
be finite. Cotangents are zero there, as the mask's ``jnp.where`` makes them.
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, ModelConfig
from repro.kernels import xl_rel
from repro.models import attention

B, H = 2, 3
# (sq, largest query tile): 37 rows in tiles of 16 cover three tiles, the
# last one partial, and accumulate dR across them
TILINGS = [(8, xl_rel.MAX_TQ), (13, xl_rel.MAX_TQ), (37, xl_rel.MAX_TQ),
           (37, 16)]
CASES = [pytest.param(sq, mem, d, dtype, tq,
                      id=f"sq{sq}-mem{mem}-d{d}-{dtype.__name__}-tq{tq}")
         for sq, tq in TILINGS for mem in (0, 8, 16) for d in (16, 41)
         for dtype in (jnp.float32, jnp.bfloat16)]


def _inputs(sq, mem, d, dtype):
    sk = sq + mem
    kq, kr, kg = jax.random.split(jax.random.PRNGKey(sq * 1000 + mem * 10 + d),
                                  3)
    qv = jax.random.normal(kq, (B, sq, H, d), jnp.float32).astype(dtype)
    r = jax.random.normal(kr, (sk, H, d), jnp.float32).astype(dtype)
    valid = np.arange(sk)[None, :] <= (sk - sq) + np.arange(sq)[:, None]
    g = (jax.random.normal(kg, (B, H, sq, sk), jnp.float32)
         * valid).astype(dtype)
    return qv, r, g, valid


def _by_distance(qv, r):
    """The definition, as an explicit gather of r at j + sq - 1 - i."""
    sq, sk = qv.shape[1], r.shape[0]
    i, j = np.arange(sq)[:, None], np.arange(sk)[None, :]
    dist = np.clip(j + sq - 1 - i, 0, sk - 1)
    return jnp.einsum("bihd,ijhd->bhij", qv.astype(jnp.float32),
                      r.astype(jnp.float32)[dist],
                      precision=jax.lax.Precision.HIGHEST)


def _xla_bd(qv, r):
    return attention._rel_shift(jnp.einsum("bqhd,khd->bhqk", qv, r))


def _ulp(x):
    """One unit in the last place of x in x's dtype (bf16: 8 bits)."""
    bits = 24 if x.dtype == jnp.float32 else 8
    _, e = np.frexp(np.abs(np.asarray(x, np.float32)))
    return np.ldexp(1.0, e - bits)


def _close(got, want, valid, dtype):
    """Within one ulp of got's dtype at the larger of each value and the
    tensor's largest (sums of signed terms cancel)."""
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    scale = np.maximum(np.abs(want), np.abs(want).max())
    tol = _ulp(jnp.asarray(scale, dtype))
    if dtype == jnp.float32:
        tol = tol * 16            # f32 sums of up to 41 products, any order
    err = np.where(valid, np.abs(got - want) - tol, 0)
    assert err.max() <= 0, float(err.max())


@pytest.fixture
def max_tq(monkeypatch):
    def set_(tq):
        monkeypatch.setattr(xl_rel, "MAX_TQ", tq)
    return set_


@pytest.mark.parametrize("sq,mem,d,dtype,tq", CASES)
def test_forward_matches_definition_and_xla_path(sq, mem, d, dtype, tq,
                                                  max_tq):
    max_tq(tq)
    qv, r, _, valid = _inputs(sq, mem, d, dtype)
    got = xl_rel.xl_rel_bd(qv, r, interpret=True)
    assert got.shape == (B, H, sq, sq + mem) and got.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, _by_distance(qv, r), valid, dtype)
    _close(got, _xla_bd(qv, r), valid, dtype)


@pytest.mark.parametrize("sq,mem,d,dtype,tq", CASES)
def test_vjp_matches_xla_path(sq, mem, d, dtype, tq, max_tq):
    max_tq(tq)
    qv, r, g, _ = _inputs(sq, mem, d, dtype)
    kernel = functools.partial(xl_rel.xl_rel_bd, interpret=True)
    got = jax.vjp(kernel, qv, r)[1](g)
    want = jax.vjp(_xla_bd, qv, r)[1](g)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        _close(a, b, np.ones(a.shape, bool), dtype)


def test_xl_attention_on_the_kernel_path(monkeypatch):
    """The whole of xl_attention with the kernel forced, against the XLA path:
    output and the gradients of q, k, v and the position parameters."""
    a = AttentionConfig(n_heads=H, n_kv_heads=H, head_dim=41, kind="xl_rel")
    cfg = ModelConfig(d_model=96, attention=a)
    params = attention.init_attention(jax.random.PRNGKey(0), cfg)
    params["u_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                               (H, 41))
    params["v_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                               (H, 41))
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    sq, sk = 13, 29
    q = jax.random.normal(ks[0], (B, sq, H, 41))
    k = jax.random.normal(ks[1], (B, sk, H, 41))
    v = jax.random.normal(ks[2], (B, sk, H, 41))
    w = jax.random.normal(ks[3], (B, sq, H, 41))

    def loss(params, q, k, v):
        out = attention.xl_attention(params, q, k, v, a, cfg.d_model)
        return jnp.sum(out * w), out

    want = jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(params, q, k, v)
    calls = []

    def kernel(qv, r):
        calls.append(qv.shape)
        return xl_rel.xl_rel_bd(qv, r, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "xl_rel_bd", kernel)
    got = jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(params, q, k, v)
    assert calls
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


# Under a mesh the kernel runs per device inside a shard_map (GSPMD cannot
# partition a Mosaic call): batch over the data axes and heads over 'model'
# where they divide, heads whole where they do not (6 heads over 4). Run with
# four forced host devices in a subprocess, so this process keeps its one.
_MESH_CHECK = """
import functools, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import xl_rel
from repro.launch.mesh import make_mesh
from repro.models import attention
from repro.sharding import mesh_context

b, h, d, sq, sk = 4, 6, 41, 13, 29
ks = jax.random.split(jax.random.PRNGKey(0), 3)
qv = jax.random.normal(ks[0], (b, sq, h, d))
r = jax.random.normal(ks[1], (sk, h, d))
valid = np.arange(sk)[None, :] <= (sk - sq) + np.arange(sq)[:, None]
g = jax.random.normal(ks[2], (b, h, sq, sk)) * valid
want, vjp = jax.vjp(attention._rel_bd, qv, r)
want_ct = vjp(g)
calls = []
def kernel(qv, r):
    calls.append(qv.shape)
    return xl_rel.xl_rel_bd(qv, r, interpret=True)
attention.xl_rel_bd = kernel
jax.default_backend = lambda: "tpu"
dims = tuple(int(x) for x in sys.argv[1].split("x"))
axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
mesh = make_mesh(dims, axes)
heads = "model" if h % mesh.shape["model"] == 0 else None
batch = axes[:-1]
put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
with mesh_context(mesh):
    def fn(qv, r, g):
        out, vjp = jax.vjp(attention._rel_bd, qv, r)
        return out, vjp(g)
    got, ct = jax.jit(fn)(put(qv, batch, None, heads), put(r, None, heads),
                          put(g, batch, heads))
# one device's block: the batch split over data (and pod), heads over model
# where they divide
local = (b // (mesh.size // mesh.shape["model"]), sq,
         h // mesh.shape["model"] if heads else h, d)
assert calls == [local], (calls, local)
np.testing.assert_allclose(np.where(valid, got, 0), np.where(valid, want, 0),
                           rtol=1e-5, atol=1e-5)
for x, y in zip(ct, want_ct):
    np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)
print("MESH_OK")
"""


@pytest.mark.parametrize("mesh", ["2x2", "4x1", "1x4", "2x1x2"])
def test_kernel_per_device_under_a_mesh(mesh):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_MESH_CHECK),
                        mesh], env=env, cwd=repo, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0 and "MESH_OK" in r.stdout, r.stderr[-3000:]
