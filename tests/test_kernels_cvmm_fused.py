"""Fused CVMM pipeline validation: the ``CvmmPlan`` layout object and the
gather->grouped-GEMM->epilogue kernels (interpret mode on CPU) against the
``ragged`` / pure-jnp oracles — forward, gradients, empty experts, E-padding,
and the plan-reuse regression (backward must not re-derive the layout)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import cvmm, ops

# (N_tokens, d_model, E, expert_size G, K, n_valid_experts)
# n_valid < E models EP-padding: experts >= n_valid are never routed to.
CASES = [
    (40, 24, 5, 16, 2, 5),
    (64, 32, 4, 32, 2, 3),        # padded expert (idx never reaches expert 3)
    (9, 8, 3, 8, 1, 2),           # tiny, under one tile, K=1
    (150, 48, 6, 24, 4, 6),
    (32, 16, 2, 16, 2, 1),        # all tokens on one expert, one empty
]


def _mk(case, dtype, seed=0):
    n, d, e, g, k, e_valid = case
    key = jax.random.PRNGKey(seed + n * 13 + d)
    kx, ki, kg, k1, k2, k3 = jax.random.split(key, 6)
    xf = jax.random.normal(kx, (n, d), jnp.float32).astype(dtype)
    idx = jax.random.randint(ki, (n, k), 0, e_valid)
    gates = jax.nn.softmax(jax.random.normal(kg, (n, k), jnp.float32), -1)
    w1 = (0.3 * jax.random.normal(k1, (e, d, g), jnp.float32)).astype(dtype)
    w1g = (0.3 * jax.random.normal(k2, (e, d, g), jnp.float32)).astype(dtype)
    w2 = (0.3 * jax.random.normal(k3, (e, g, d), jnp.float32)).astype(dtype)
    return xf, idx, gates, w1, w1g, w2


def _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, act):
    """Unfused reference on the ragged-dot backend (differentiable)."""
    n, k = idx.shape
    e_flat = idx.reshape(-1)
    g_flat = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(n), k)
    perm = jnp.argsort(e_flat, stable=True)
    gs = jnp.bincount(e_flat, length=e).astype(jnp.int32)
    xs = xf[tok[perm]]
    h = jax.lax.ragged_dot(xs, w1.astype(xs.dtype), gs)
    u = act(h)
    if w1g is not None:
        u = u * jax.lax.ragged_dot(xs, w1g.astype(xs.dtype), gs)
    y = jax.lax.ragged_dot(u, w2.astype(u.dtype), gs)
    y = y * g_flat[perm][:, None].astype(y.dtype)
    return jnp.zeros_like(xf).at[tok[perm]].add(y)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("glu", [False, True])
def test_fused_forward_matches_ragged(case, dtype, glu):
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, dtype)
    if not glu:
        w1g = None
    plan = ops.make_moe_plan(idx, gates, n, e)
    got = ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="relu",
                            interpret=True)
    want = _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.relu)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", CASES[:3])
@pytest.mark.parametrize("glu", [False, True])
def test_fused_gradients_match_ragged(case, glu):
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)
    if not glu:
        w1g = None
    act = lambda x: jax.nn.gelu(x, approximate=True)
    probe = lambda y: jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    def loss_fused(xf, gates, w1, w1g, w2):
        plan = ops.make_moe_plan(idx, gates, n, e)
        return probe(ops.moe_mlp_fused(xf, plan, w1, w2, w1g,
                                       activation="gelu", interpret=True))

    def loss_ref(xf, gates, w1, w1g, w2):
        return probe(_oracle_mlp(xf, idx, gates, w1, w1g, w2, e, act))

    if glu:
        gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(xf, gates, w1, w1g, w2)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(xf, gates, w1, w1g, w2)
        names = ("dx", "dgates", "dw1", "dw1g", "dw2")
    else:
        f2 = lambda fn: (lambda xf, gates, w1, w2: fn(xf, gates, w1, None, w2))
        gf = jax.grad(f2(loss_fused), argnums=(0, 1, 2, 3))(xf, gates, w1, w2)
        gr = jax.grad(f2(loss_ref), argnums=(0, 1, 2, 3))(xf, gates, w1, w2)
        names = ("dx", "dgates", "dw1", "dw2")
    for name, a, b in zip(names, gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


# (N_tokens, d_model, E, G, K, held_share): with held_share, 3 of 4 routed
# rows go to experts held elsewhere (id E, gate 0, as core/moe.held_share
# gives them): the drop_unheld plan's live bound sits far below its grid.
# The cases share one shape, so each function below compiles once per
# interpreter; the held-share case runs its bounded kernels in the TPU
# interpreter that leaves unwritten memory NaN.
LIVE_CASES = {"held_share": (300, 24, 2, 16, 2, True),
              "all_held": (300, 24, 2, 16, 2, False)}


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_vjp(idx, args, cot, all_live, interpret):
    """The fused MLP's output and VJP under a drop_unheld plan, its live
    bound raised to the whole grid where ``all_live``."""
    n, e = args[0].shape[0], args[2].shape[0]

    def mlp(xf, gates, w1, w1g, w2):
        plan = ops.make_moe_plan(idx, gates, n, e, drop_unheld=True)
        plan = plan._replace(n_live=jnp.where(
            all_live, plan.m_pad // ops.TM, plan.n_live))
        return ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="gelu",
                                 interpret=interpret)

    y, vjp = jax.vjp(mlp, *args)
    return (y,) + vjp(cot)


@jax.jit
def _oracle_vjp(idx, args, cot):
    act = lambda x: jax.nn.gelu(x, approximate=True)
    e = args[2].shape[0]
    y, vjp = jax.vjp(lambda xf, *a: _oracle_mlp(xf, idx, *a, e, act), *args)
    return (y,) + vjp(cot)


@pytest.mark.parametrize("name", sorted(LIVE_CASES))
def test_live_bound_matches_all_live(name):
    """Kernels that stop at the plan's live bound give the forward, dX, dW1,
    dW1g, dW2 and d(gate) of the same plan with every tile live bit for bit,
    and the ragged oracle's within test_fused_gradients_match_ragged's
    tolerances. With a held share the bounded kernels run in the TPU
    interpreter that leaves unwritten memory NaN (outputs, scratch and DMA
    targets), so nothing the kernels leave unwritten past the bound reaches
    a result."""
    n, d, e, g, k, share = LIVE_CASES[name]
    interpret = (pltpu.InterpretParams(uninitialized_memory="nan") if share
                 else True)
    xf, idx, gates, w1, w1g, w2 = _mk((n, d, e, g, k, e), jnp.float32)
    if share:
        away = jax.random.bernoulli(jax.random.PRNGKey(11), 0.75, idx.shape)
        idx, gates = jnp.where(away, e, idx), jnp.where(away, 0.0, gates)
    gs = np.bincount(np.asarray(idx).ravel(), minlength=e + 1)[:e]
    n_live = int(np.sum(-(-gs // ops.TM)))
    assert n_live < ops.grid_tiles(n * k, e) // (2 if share else 1)
    args = (xf, gates, w1, w1g, w2)
    cot = jax.random.normal(jax.random.PRNGKey(12), xf.shape)
    got = _fused_vjp(idx, args, cot, False, interpret)
    every = _fused_vjp(idx, args, cot, True, True)
    want = _oracle_vjp(idx, args, cot)
    names = ("y", "dx", "dgates", "dw1", "dw1g", "dw2")
    for name, a, b, c in zip(names, got, every, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
        tol = 1e-5 if name == "y" else 2e-4
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=tol,
                                   rtol=tol, err_msg=name)


def test_fused_empty_expert_weight_grads_zero():
    """Experts that receive no rows must get exactly-zero weight gradients."""
    case = (64, 32, 4, 32, 2, 3)          # expert 3 never selected
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)

    def loss(w1, w1g, w2):
        plan = ops.make_moe_plan(idx, gates, n, e)
        return ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="relu",
                                 interpret=True).sum()

    d1, d1g, d2 = jax.grad(loss, argnums=(0, 1, 2))(w1, w1g, w2)
    for dw in (d1, d1g, d2):
        assert np.all(np.asarray(dw[3]) == 0)
        assert np.any(np.asarray(dw[0]) != 0)


def test_fused_bf16_gradients_finite_and_close():
    case = (40, 24, 5, 16, 2, 5)
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.bfloat16)

    def loss(xf, w1, w2):
        plan = ops.make_moe_plan(idx, gates, n, e)
        y = ops.moe_mlp_fused(xf, plan, w1, w2, None, activation="relu",
                              interpret=True)
        return y.astype(jnp.float32).sum()

    gx, g1, g2 = jax.grad(loss, argnums=(0, 1, 2))(xf, w1, w2)

    def loss_ref(xf, w1, w2):
        y = _oracle_mlp(xf, idx, gates, w1, None, w2, e, jax.nn.relu)
        return y.astype(jnp.float32).sum()

    rx, r1, r2 = jax.grad(loss_ref, argnums=(0, 1, 2))(xf, w1, w2)
    for a, b in ((gx, rx), (g1, r1), (g2, r2)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, atol=0.15, rtol=0.15)


@pytest.mark.parametrize("drop_unheld", [False, True])
def test_plan_layout_consistency(drop_unheld):
    """row_src/gate_tiles/new_pos describe the same permutation, and the
    live bound counts the tiles that hold rows. ``drop_unheld``: ids equal
    to E are rows held elsewhere, which get no slot."""
    case = (300, 16, 6, 8, 3, 5)
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)
    if drop_unheld:
        idx = jnp.where(jnp.arange(n)[:, None] % 3 == 0, idx, e)
    plan = ops.make_moe_plan(idx, gates, n, e, drop_unheld=drop_unheld)
    assert plan.row_src.shape[0] == plan.m_pad
    assert plan.m_pad % ops.TM == 0
    row_src = np.asarray(plan.row_src)
    e_flat = np.asarray(idx.reshape(-1))
    held = int((e_flat < e).sum())
    new_pos = np.asarray(plan.new_pos)
    tok = np.repeat(np.arange(n), k)
    perm = np.asarray(plan.perm)
    assert (new_pos[held:] == plan.m_pad).all()     # held elsewhere: no slot
    new_pos, perm = new_pos[:held], perm[:held]
    # every sorted row's slot points back at its source token
    assert (row_src[new_pos] == tok[perm]).all()
    # slack slots hold the sentinel and a zero gate
    gate_pad = np.asarray(plan.gate_tiles).reshape(-1)
    slack = np.ones(plan.m_pad, bool)
    slack[new_pos] = False
    assert (row_src[slack] == n).all()
    assert (gate_pad[slack] == 0).all()
    # tiles are expert-pure: each valid slot's tile maps to its row's expert
    te = np.asarray(plan.tile_expert)
    e_sorted = e_flat[perm]
    assert (te[new_pos // ops.TM] == e_sorted).all()
    # the live bound: sum(ceil(gs / TM)), every slot past it slack
    gs = np.bincount(e_flat, minlength=e + 1)[:e]
    n_live = int(np.sum(-(-gs // ops.TM)))
    np.testing.assert_array_equal(plan.n_live, [n_live])
    assert n_live == ops.live_tiles(plan.group_sizes)[0]
    assert (row_src[n_live * ops.TM:] == n).all()
    if drop_unheld:
        assert 2 * n_live < plan.m_pad // ops.TM


def test_backward_reuses_forward_plan(monkeypatch):
    """Regression: the backward pass must NOT re-derive the tile layout.

    The seed implementation traced ``_tile_layout`` three times per grad call
    (forward, dX, dW); the planned custom_vjp must trace it exactly once."""
    calls = {"n": 0}
    orig = ops._tile_layout

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(ops, "_tile_layout", counting)

    m, k, n, e = 64, 32, 16, 4
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = 0.1 * jax.random.normal(key, (e, k, n), jnp.float32)
    gs = jnp.array([10, 20, 30, 4])
    jax.grad(lambda x, w: ops.cvmm(x, gs, w, impl="pallas_interpret").sum(),
             argnums=(0, 1))(x, w)
    assert calls["n"] == 1, f"_tile_layout traced {calls['n']}x (expected 1)"

    # fused pipeline: one plan per MoE call, zero extra layout derivations
    calls["n"] = 0
    case = (40, 24, 5, 16, 2, 5)
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)

    def loss(xf, w1, w2):
        plan = ops.make_moe_plan(idx, gates, 40, 5)
        return ops.moe_mlp_fused(xf, plan, w1, w2, None, activation="relu",
                                 interpret=True).sum()

    jax.grad(loss, argnums=(0, 1, 2))(xf, w1, w2)
    assert calls["n"] == 1, f"_tile_layout traced {calls['n']}x (expected 1)"


def test_fused_n_rows_not_multiple_of_8():
    """The streamed kernel gathers rows straight from HBM: no multiple-of-8
    row-count requirement (the retired whole-x kernel needed xe padded)."""
    case = (13, 24, 3, 16, 2, 3)
    n, d, e, g, k, _ = case
    assert n % 8 != 0
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    got = ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="relu",
                            interpret=True)
    want = _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    def loss(xf, w1, w2):
        plan = ops.make_moe_plan(idx, gates, n, e)
        return ops.moe_mlp_fused(xf, plan, w1, w2, None, activation="gelu",
                                 interpret=True).sum()

    def loss_ref(xf, w1, w2):
        act = lambda x: jax.nn.gelu(x, approximate=True)
        return _oracle_mlp(xf, idx, gates, w1, None, w2, e, act).sum()

    gf = jax.grad(loss, argnums=(0, 1, 2))(xf, w1, w2)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(xf, w1, w2)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_fused_all_slack_final_tile():
    """A row tile whose every row_src slot is the sentinel: the streamed gather
    issues ZERO DMAs for it (slack rows are skipped, not clamped-gathered) and
    the zero-filled scratch must yield finite outputs that are dropped."""
    n, d, e, g, k = 16, 16, 2, 8, 1
    key = jax.random.PRNGKey(3)
    kx, kg, k1, k2 = jax.random.split(key, 4)
    xf = jax.random.normal(kx, (n, d), jnp.float32)
    idx = jnp.zeros((n, k), jnp.int32)            # every token -> expert 0
    gates = jax.nn.softmax(jax.random.normal(kg, (n, k), jnp.float32), -1)
    w1 = 0.3 * jax.random.normal(k1, (e, d, g), jnp.float32)
    w2 = 0.3 * jax.random.normal(k2, (e, g, d), jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    row_src = np.asarray(plan.row_src).reshape(-1, ops.TM)
    assert (row_src[-1] == n).all(), "test setup: final tile must be all-slack"
    got = ops.moe_mlp_fused(xf, plan, w1, w2, None, activation="relu",
                            interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    want = _oracle_mlp(xf, idx, gates, w1, None, w2, e, jax.nn.relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # gradients also stay finite and match (all-slack tiles contribute zero)
    g1 = jax.grad(lambda w1: ops.moe_mlp_fused(
        xf, ops.make_moe_plan(idx, gates, n, e), w1, w2, None,
        activation="relu", interpret=True).sum())(w1)
    r1 = jax.grad(lambda w1: _oracle_mlp(
        xf, idx, gates, w1, None, w2, e, jax.nn.relu).sum())(w1)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(r1),
                               atol=2e-4, rtol=2e-4)


def test_fused_single_expert_plan():
    """E=1 degenerates to a dense MLP with a gate; the streamed plan must
    handle a single expert (single weight block, one contiguous group)."""
    n, d, e, g, k = 37, 16, 1, 8, 1
    case = (n, d, e, g, k, e)
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    got = ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="silu",
                            interpret=True)
    want = _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.silu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_gather_rows_pallas_matches_take():
    """The streamed gather primitive == jnp.take with zero fill on sentinels."""
    n, d, e, k = 45, 24, 4, 2
    case = (n, d, e, 16, k, e)
    xf, idx, gates, *_ = _mk(case, jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    xe = ops._pad_lane(xf, 1)
    got = cvmm.cvmm_gather_rows_pallas(xe, plan.row_src, plan.run_start,
                                       plan.run_off, interpret=True)
    want = jnp.take(xe, plan.row_src, axis=0, mode="fill", fill_value=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def _replay_runs(plan, n_rows, x):
    """Chunk-table replay via the shared invariant oracle: this suite used to
    carry its own numpy re-execution; repro.analysis.plans is now the single
    source of those checks (CI's analysis gate runs the same code), so the
    test only asserts the oracle reports the plan clean."""
    from repro.analysis.plans import replay_chunk_table
    out, n_dma, findings = replay_chunk_table(plan, n_rows, x)
    assert findings == [], "\n".join(str(f) for f in findings)
    return out, n_dma


@pytest.mark.parametrize("case,skew", [((100, 16, 6, 8, 3, 5), False),
                                       ((300, 16, 3, 8, 1, 3), True)])
def test_plan_run_metadata_replays_gather(case, skew):
    """run_start/run_len describe exactly the row_src gather: replaying the
    chunk table in numpy reproduces take-with-zero-fill, never issues more
    descriptors than one-per-row, and fully batches contiguous tiles."""
    n, d, e, g, k, e_valid = case
    xf, idx, gates, *_ = _mk(case, jnp.float32)
    if skew:
        idx = jnp.zeros((n, k), jnp.int32)          # K=1, all rows -> expert 0
    plan = ops.make_moe_plan(idx, gates, n, e)
    x = np.asarray(ops._pad_lane(xf, 1))
    got, n_dma = _replay_runs(plan, n, x)
    want = np.asarray(jnp.take(jnp.asarray(x), plan.row_src, axis=0,
                               mode="fill", fill_value=0))
    np.testing.assert_array_equal(got, want)
    per_row = int((np.asarray(plan.row_src) < n).sum())
    assert 0 < n_dma <= per_row
    if skew:
        # fully contiguous row_src: every full tile is ONE size-TM descriptor
        rl = np.asarray(plan.run_len)
        assert int((rl == ops.TM).sum()) == n // ops.TM
        assert n_dma < per_row // 8


def test_fused_bwd_is_gather_free(monkeypatch):
    """Regression for the streamed backward: _fused_bwd must not materialize
    tile-aligned gathers via cvmm_gather_rows_pallas — dW/dX stream their
    unsorted operands straight from HBM."""
    def boom(*a, **kw):
        raise AssertionError("backward materialized a gather in HBM")

    monkeypatch.setattr(cvmm, "cvmm_gather_rows_pallas", boom)
    # ops.py no longer even imports the gather primitive; raising=False keeps
    # this tripwire armed should a future change reintroduce the import.
    monkeypatch.setattr(ops, "cvmm_gather_rows_pallas", boom, raising=False)

    case = (40, 24, 5, 16, 2, 5)
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)

    def loss(xf, w1, w1g, w2):
        plan = ops.make_moe_plan(idx, gates, n, e)
        return ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="relu",
                                 interpret=True).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(xf, w1, w1g, w2)
    assert all(np.isfinite(np.asarray(gr)).all() for gr in grads)


@pytest.mark.parametrize("stream_x", [True, False])
def test_dw_streamed_matches_unfused_dw(stream_x):
    """The streamed dW kernel == the unfused dW kernel fed the materialized
    gather, for both streamed sides (dW1's x-operand, dW2's gated g-operand)."""
    case = (52, 24, 4, 16, 2, 4)
    n, d, e, g, k, _ = case
    xf, idx, gates, _, _, _ = _mk(case, jnp.float32)
    key = jax.random.PRNGKey(7)
    plan = ops.make_moe_plan(idx, gates, n, e)
    xe = ops._pad_lane(xf, 1)
    d_pad, g_pad = xe.shape[1], ops.round_up(g, ops.LANE)
    x_pad = jnp.take(xe, plan.row_src, axis=0, mode="fill", fill_value=0)
    runs = (plan.row_src, plan.run_start, plan.run_off, plan.tile_expert)
    if stream_x:
        gg = jax.random.normal(key, (plan.m_pad, g_pad), jnp.float32)
        got = cvmm.cvmm_dw_streamed_pallas(xe, gg, *runs, e, stream_x=True,
                                           interpret=True)
        want = cvmm.cvmm_dw_pallas(x_pad, plan.tile_expert, gg, e,
                                   interpret=True)
    else:
        u = jax.random.normal(key, (plan.m_pad, g_pad), jnp.float32)
        got = cvmm.cvmm_dw_streamed_pallas(u, xe, *runs, e, stream_x=False,
                                           gate_tiles=plan.gate_tiles,
                                           interpret=True)
        gate = plan.gate_tiles.reshape(-1)[:, None]
        want = cvmm.cvmm_dw_pallas(u, plan.tile_expert, x_pad * gate, e,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_fused_supported_streams_past_whole_x_budget():
    """Regression for the lifted residency gate: the retired kernel kept the
    whole (N, K) activation block in VMEM and ``fused_supported`` rejected
    token counts past that budget; the streamed kernel must accept >= 4x the
    old boundary (and far beyond), while still rejecting non-tile-local
    activations and tile working sets that genuinely cannot fit."""
    d_model, g = 128, 128
    for dtype, glu in ((jnp.float32, True), (jnp.float32, False),
                      (jnp.bfloat16, True)):
        n_weights = 2 if glu else 1
        old = cvmm.legacy_whole_x_rows(d_model, jnp.dtype(dtype).itemsize,
                                       n_weights, n_out=1 + n_weights)
        assert old > 0
        for mult in (1, 4, 64):
            assert ops.fused_supported(mult * old, d_model, g, "relu",
                                       dtype, glu=glu)
    # still rejected: non-tile-local activation ...
    assert not ops.fused_supported(64, d_model, g, "softmax")
    # ... and a d_model whose per-step TILE working set exceeds VMEM
    assert not ops.fused_supported(64, 1_000_000, g, "relu")


def test_moe_sort_dispatch_uses_fused(monkeypatch):
    """apply_moe(dispatch='sort') routes through the fused pipeline when the
    default impl is pallas_fused, and matches the ragged-backed sort path."""
    from repro.configs import moe_ffn
    from repro.core import apply_moe, init_moe

    d_model, ne, g, k = 32, 4, 16, 2
    cfg = moe_ffn(ne, g, k, dispatch="sort")
    p = init_moe(jax.random.PRNGKey(0), d_model, cfg, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, d_model), jnp.float32)

    ops.set_default_impl("ragged")
    try:
        y_ref, _ = apply_moe(p, x, cfg)
    finally:
        ops.set_default_impl(None)

    called = {"fused": 0}
    orig = ops.moe_mlp_fused

    def spy(*a, **kw):
        called["fused"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(ops, "moe_mlp_fused", spy)
    ops.set_default_impl("pallas_fused")
    try:
        y_fused, _ = apply_moe(p, x, cfg)
    finally:
        ops.set_default_impl(None)
    assert called["fused"] == 1
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("activation,expect_fused", [
    ("relu", True),        # tile-local: gate says fused
    ("gelu", True),
    ("softmax", False),    # not tile-local: gate must force the unfused path
])
def test_moe_dispatch_consistent_with_gate(monkeypatch, activation,
                                           expect_fused):
    """apply_moe(dispatch='sort') under impl=pallas_fused must pick the fused
    vs unfused pipeline exactly as ``fused_supported`` answers — and both
    choices must agree numerically with the ragged-backed sort path."""
    from repro.configs import moe_ffn
    from repro.core import apply_moe, init_moe

    d_model, ne, g, k = 32, 4, 16, 2
    cfg = moe_ffn(ne, g, k, dispatch="sort", activation=activation)
    p = init_moe(jax.random.PRNGKey(0), d_model, cfg, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d_model), jnp.float32)

    assert ops.fused_supported(x.shape[0] * x.shape[1], d_model, g,
                               activation, x.dtype, glu=False) == expect_fused

    ops.set_default_impl("ragged")
    try:
        y_ref, _ = apply_moe(p, x, cfg)
    finally:
        ops.set_default_impl(None)

    called = {"fused": 0}
    orig = ops.moe_mlp_fused

    def spy(*a, **kw):
        called["fused"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(ops, "moe_mlp_fused", spy)
    ops.set_default_impl("pallas_fused")
    try:
        y, _ = apply_moe(p, x, cfg)
    finally:
        ops.set_default_impl(None)
    assert called["fused"] == (1 if expect_fused else 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Deeper stream pipelines (PR 6): the autotuner may pick n_buffers > 2; every
# streamed kernel must stay bit-compatible with the depth-2 default.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_buffers", [3, 4])
def test_gather_rows_deeper_pipeline_matches_take(n_buffers):
    n, d, e, k = 45, 24, 4, 2
    case = (n, d, e, 16, k, e)
    xf, idx, gates, *_ = _mk(case, jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    xe = ops._pad_lane(xf, 1)
    got = cvmm.cvmm_gather_rows_pallas(xe, plan.row_src, plan.run_start,
                                       plan.run_off, interpret=True,
                                       n_buffers=n_buffers)
    want = jnp.take(xe, plan.row_src, axis=0, mode="fill", fill_value=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_dw_streamed_depth3_matches_depth2():
    case = (52, 24, 4, 16, 2, 4)
    n, d, e, g, k, _ = case
    xf, idx, gates, _, _, _ = _mk(case, jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    xe = ops._pad_lane(xf, 1)
    g_pad = ops.round_up(g, ops.LANE)
    gg = jax.random.normal(jax.random.PRNGKey(7), (plan.m_pad, g_pad),
                           jnp.float32)
    runs = (plan.row_src, plan.run_start, plan.run_off, plan.tile_expert)
    d2 = cvmm.cvmm_dw_streamed_pallas(xe, gg, *runs, e, stream_x=True,
                                      interpret=True)
    d3 = cvmm.cvmm_dw_streamed_pallas(xe, gg, *runs, e, stream_x=True,
                                      interpret=True, n_buffers=3)
    np.testing.assert_allclose(np.asarray(d3), np.asarray(d2))


@pytest.mark.parametrize("glu", [False, True])
def test_fused_mlp_depth3_tiles_match_ragged(glu):
    """moe_mlp_fused with an explicit depth-3 FusedTiles plan (as a tuned
    cache would supply) matches the ragged oracle forward AND backward —
    including the 1-token-tile warmup guard on small grids."""
    case = (40, 24, 5, 16, 2, 5)
    n, d, e, g, k, _ = case
    xf, idx, gates, w1, w1g, w2 = _mk(case, jnp.float32)
    if not glu:
        w1g = None
    base = ops.fused_mlp_tiles(d, g, xf.dtype, glu=glu)
    tiles = base._replace(w1_nb=3, w1_train_nb=3, t0_nb=3, dw_nb=3)

    def loss_fused(xf, gates, w1, w1g, w2):
        plan = ops.make_moe_plan(idx, gates, n, e)
        return ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="relu",
                                 interpret=True, tiles=tiles).sum()

    def loss_ref(xf, gates, w1, w1g, w2):
        return _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.relu).sum()

    y = ops.moe_mlp_fused(xf, ops.make_moe_plan(idx, gates, n, e), w1, w2,
                          w1g, activation="relu", interpret=True, tiles=tiles)
    want = _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    argnums = (0, 1, 2, 3, 4) if glu else (0, 1, 2, 4)
    gf = jax.grad(loss_fused, argnums=argnums)(xf, gates, w1, w1g, w2)
    gr = jax.grad(loss_ref, argnums=argnums)(xf, gates, w1, w1g, w2)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# Boundary grids: fewer row tiles than pipeline buffers. The warmup must not
# issue tiles past the grid and the drain must still cover every tile — the
# exact regime the analysis pipeline pass proves symbolically; these runs
# confirm the proven schedule end-to-end through the real kernels.
# ---------------------------------------------------------------------------

# (n, d, e, g, k): m_pad/TM = ceil(n*k/TM) + e tiles — 2 tiles for e=1,
# 3 tiles for e=2, both strictly under the deepest pipeline.
_BOUNDARY_CASES = [(20, 16, 1, 8, 1), (20, 16, 2, 8, 1)]


@pytest.mark.parametrize("n_buffers", [3, 4])
@pytest.mark.parametrize("case", _BOUNDARY_CASES)
def test_fused_mlp_boundary_tiles_lt_buffers(case, n_buffers):
    n, d, e, g, k = case
    xf, idx, gates, w1, w1g, w2 = _mk((n, d, e, g, k, e), jnp.float32)
    plan = ops.make_moe_plan(idx, gates, n, e)
    n_tiles = plan.m_pad // ops.TM
    if n_tiles >= n_buffers:
        pytest.skip(f"grid has {n_tiles} tiles, not a boundary at depth "
                    f"{n_buffers}")
    base = ops.fused_mlp_tiles(d, g, xf.dtype, glu=True)
    tiles = base._replace(w1_nb=n_buffers, w1_train_nb=n_buffers,
                          t0_nb=n_buffers, dw_nb=n_buffers)

    y = ops.moe_mlp_fused(xf, plan, w1, w2, w1g, activation="relu",
                          interpret=True, tiles=tiles)
    want = _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    def loss_fused(xf, gates, w1, w1g, w2):
        p = ops.make_moe_plan(idx, gates, n, e)
        return ops.moe_mlp_fused(xf, p, w1, w2, w1g, activation="relu",
                                 interpret=True, tiles=tiles).sum()

    def loss_ref(xf, gates, w1, w1g, w2):
        return _oracle_mlp(xf, idx, gates, w1, w1g, w2, e, jax.nn.relu).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(xf, gates, w1, w1g, w2)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(xf, gates, w1, w1g, w2)
    for name, a, b in zip(("dx", "dgates", "dw1", "dw1g", "dw2"), gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("n_buffers", [3, 4])
def test_gather_rows_single_tile_boundary(n_buffers):
    """A one-tile gather plan (n*s <= TM) at every deep pipeline: pure warmup
    + drain, no steady state at all."""
    n, rows, s = 30, 200, 4
    key = jax.random.PRNGKey(5)
    idx = jax.random.randint(key, (n, s), 0, rows)
    w = jnp.ones((n, s), jnp.float32)
    plan = ops.make_gather_plan(idx, w, rows)
    assert plan.row_src.shape[0] == ops.TM          # exactly one tile
    x = jax.random.normal(key, (rows, 2 * ops.LANE), jnp.float32)
    got = cvmm.cvmm_gather_rows_pallas(x, plan.row_src, plan.run_start,
                                       plan.run_off, interpret=True,
                                       n_buffers=n_buffers)
    want = jnp.take(x, plan.row_src, axis=0, mode="fill", fill_value=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
