"""Seeded-mutant validation of the kernel-contract analyzer.

Each test plants one representative bug in the REAL production artifact the
pass verifies (the shared schedule skeleton, the tuner's working-set
accounting, the sharding table) and asserts the pass flags it; the pinned
snapshot test asserts the current tree is clean AND that each pass keeps
verifying at least as many facts as it did when this suite was written — a
pass that silently stops checking cannot hide behind an empty findings list.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.analysis import run_passes
from repro.analysis.pipeline import check_pipeline, check_stream
from repro.analysis.plans import check_plans, replay_chunk_table, verify_plan
from repro.analysis.sharding import check_sharding
from repro.analysis.vmem import check_vmem
from repro.kernels import autotune, cvmm, ops
from repro.sharding import logical


# ---------------------------------------------------------------------------
# clean tree: every pass green, check counts pinned above a floor
# ---------------------------------------------------------------------------

# Floors are ~10% under the counts at the time this suite was written
# (pipeline 4374, plans 176, vmem 53120, sharding 1689): growth is free,
# silent shrinkage of a sweep fails here.
_CHECK_FLOORS = {"pipeline": 4000, "plans": 150, "vmem": 45000,
                 "sharding": 1500}


def test_current_tree_is_clean_and_sweeps_stay_wide():
    report = run_passes(("pipeline", "plans", "vmem", "sharding"))
    assert report.ok, "\n".join(str(f) for f in report.findings)
    for name, floor in _CHECK_FLOORS.items():
        assert report.checks[name] >= floor, (
            f"{name} pass verified only {report.checks[name]} facts "
            f"(floor {floor}) — did a sweep silently shrink?")


# ---------------------------------------------------------------------------
# mutant 1: dropped wait in the shared DMA schedule skeleton
# ---------------------------------------------------------------------------

def test_pipeline_flags_dropped_wait(monkeypatch):
    def mutant(i, m_tiles, n_buffers, *, issue, wait, when):
        when(i == 0, lambda: issue(0))
        for t in range(1, n_buffers - 1):
            when((i == 0) & (t < m_tiles), lambda t=t: issue(t))
        # wait(i) dropped: compute reads the slot while the DMA is in flight
        when(i + n_buffers - 1 < m_tiles, lambda: issue(i + n_buffers - 1))
        return cvmm.stream_slot(i, n_buffers)

    monkeypatch.setattr(cvmm, "stream_schedule_step", mutant)
    findings, _ = check_pipeline()
    kinds = {f.check for f in findings}
    assert "compute-unwaited" in kinds
    assert kinds & {"leaked-dma", "slot-overwrite", "coverage"}


# ---------------------------------------------------------------------------
# mutant 2: off-by-one warmup (unguarded prefetch past the grid)
# ---------------------------------------------------------------------------

def test_pipeline_flags_unguarded_warmup(monkeypatch):
    def mutant(i, m_tiles, n_buffers, *, issue, wait, when):
        when(i == 0, lambda: issue(0))
        for t in range(1, n_buffers - 1):
            # the (t < m_tiles) warmup guard dropped: boundary grids with
            # m_tiles < n_buffers prefetch tiles whose chunk tables and
            # scalar-prefetch rows do not exist
            when(i == 0, lambda t=t: issue(t))
        wait(i)
        when(i + n_buffers - 1 < m_tiles, lambda: issue(i + n_buffers - 1))
        return cvmm.stream_slot(i, n_buffers)

    monkeypatch.setattr(cvmm, "stream_schedule_step", mutant)
    findings, _ = check_pipeline()
    assert any(f.check == "issue-out-of-range" for f in findings)
    # only boundary grids are affected; long grids stay legal
    ok_f, _ = check_stream(8, 3, family="fused_w1")
    assert ok_f == []


# ---------------------------------------------------------------------------
# mutant 3: tuner working-set accounting under-reports -> busting candidates
# ---------------------------------------------------------------------------

def test_vmem_flags_busting_candidate(monkeypatch):
    # the classic drift: a kernel grows its scratch but the tuner's formula
    # is not updated — candidates that fit on paper crash at launch
    monkeypatch.setattr(autotune, "ws_fused_w1",
                        lambda k, tn, b, nw, no, nb=2: 0)
    findings, _ = check_vmem()
    kinds = {f.check for f in findings}
    assert "budget" in kinds and "formula-drift" in kinds
    assert any(f.check == "budget" and "fused_w1" in f.location
               for f in findings)


# ---------------------------------------------------------------------------
# mutant 4: the seed's duplicate-mesh-axis PKM rule
# ---------------------------------------------------------------------------

def test_sharding_flags_duplicate_axis_rule(monkeypatch):
    # the original seed bug: both 'heads' and 'pkm_keys' rule to 'model'
    monkeypatch.setitem(logical.PARAM_AXES, ("keys_a", 3),
                        ("heads", "embed", "pkm_keys"))
    findings, _ = check_sharding()
    dups = [f for f in findings if f.check == "duplicate-axis"]
    assert dups and any("keys_a" in f.location for f in dups)


# ---------------------------------------------------------------------------
# the plans oracle rejects corrupted plans (and ops' verify hook raises)
# ---------------------------------------------------------------------------

def _moe_plan(n=64, e=4, k=2):
    rng = np.random.RandomState(3)
    idx = jnp.asarray(rng.randint(0, e, size=(n, k)).astype(np.int32))
    gates = jnp.asarray(rng.rand(n, k).astype(np.float32))
    return ops.make_moe_plan(idx, gates, n, e), n


def test_plans_oracle_rejects_corrupted_row_src():
    # skewed routing (k=1, one expert) so multi-row DMA chunks are guaranteed
    idx = jnp.zeros((64, 1), jnp.int32)
    gates = jnp.ones((64, 1), jnp.float32)
    plan = ops.make_moe_plan(idx, gates, 64, 4)
    assert verify_plan(plan, 64) == []
    rl = np.asarray(plan.run_len)
    i = int(np.argmax(rl >= 2))      # a chunk the kernel copies as ONE DMA
    assert rl[i] >= 2
    slot = (i // 128) * 128 + int(np.asarray(plan.run_start)[i])
    rs = np.asarray(plan.row_src).copy()
    rs[slot + 1] = rs[slot]          # break the chunk's source contiguity:
    bad = plan._replace(row_src=jnp.asarray(rs))   # the DMA lands wrong rows
    assert any(f.check in ("chunk-noncontiguous", "gather-mismatch")
               for f in verify_plan(bad, 64))
    with pytest.raises(ValueError, match="plan invariant"):
        ops.plan_dma_stats(bad, 64, verify=True)


def test_plans_oracle_rejects_fetched_sentinel():
    plan, n = _moe_plan()
    rs = np.asarray(plan.row_src).copy()
    slack = np.nonzero(rs >= n)[0]
    if not slack.size:
        pytest.skip("routing produced no slack slots")
    rl = np.asarray(plan.run_len).copy()
    rst = np.asarray(plan.run_start).copy()
    rs[slack[0]] = 0                 # sentinel slot silently fetches row 0
    bad = plan._replace(row_src=jnp.asarray(rs), run_len=jnp.asarray(rl),
                        run_start=jnp.asarray(rst))
    assert any(f.check in ("sentinel-value", "sentinel-fetched", "coverage")
               for f in verify_plan(bad, n))


def test_plans_oracle_rejects_a_live_bound_below_a_row():
    # a bound one tile short: the kernels would skip a tile that holds rows
    plan, n = _moe_plan()
    assert verify_plan(plan, n) == []
    bad = plan._replace(n_live=plan.n_live - 1)
    assert any(f.check == "live-bound" for f in verify_plan(bad, n))


def test_pipeline_flags_a_prefetch_past_the_live_bound(monkeypatch):
    def mutant(i, m_tiles, n_buffers, *, issue, wait, when):
        when(i == 0, lambda: issue(0))
        for t in range(1, n_buffers - 1):
            when((i == 0) & (t < m_tiles), lambda t=t: issue(t))
        wait(i)
        # drain guard dropped: the last live steps prefetch slack tiles
        # that no step waits for
        issue(i + n_buffers - 1)
        return cvmm.stream_slot(i, n_buffers)

    monkeypatch.setattr(cvmm, "stream_schedule_step", mutant)
    findings, _ = check_pipeline()
    assert any(f.check == "issue-past-live" and "n_live=" in f.location
               for f in findings)


def test_replay_chunk_table_matches_take():
    plan, n = _moe_plan(100, 5, 3)
    x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    out, n_dma, findings = replay_chunk_table(plan, n, x)
    assert findings == [] and n_dma > 0
    rs = np.asarray(plan.row_src)
    want = np.where((rs < n)[:, None], x[np.minimum(rs, n - 1)], 0.0)
    np.testing.assert_array_equal(out, want)


def test_check_plans_clean():
    findings, checks = check_plans()
    assert findings == [] and checks > 0
