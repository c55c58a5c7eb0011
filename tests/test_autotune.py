"""Autotuner unit tests: heuristic parity with the retired static pickers,
the divisibility fix, the VMEM budget single-sourcing, and the persistent
cache lifecycle (hit-without-re-bench, corrupt/stale discard, concurrent
writers, budget invalidation).

Real micro-benchmarks never run here — tuned-mode tests inject a spy via
``autotune.set_benchmark_override`` and count invocations through
``autotune.STATS["microbench_calls"]`` (the same counter CI's cache-hit gate
reads), so the suite stays fast and deterministic in interpret-mode CI.
"""
import json
import os

import pytest

from repro.kernels import autotune, cvmm, ops
from repro.roofline import analysis


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Isolated tuner: private cache dir, clean state, disabled by default;
    restores env-driven behavior afterwards."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
    autotune.reset()
    autotune.enable(False)
    yield tmp_path
    autotune.enable(None)
    autotune.set_benchmark_override(None)
    autotune.reset()


def _spy(calls, time_of=None):
    """Fake micro-bench: records every invocation, returns ``time_of(tiles)``
    (default: constant, so roofline order decides)."""
    def fn(family, dims, tiles):
        calls.append((family, dict(dims), dict(tiles)))
        return 100.0 if time_of is None else time_of(tiles)
    return fn


# ---------------------------------------------------------------------------
# Heuristic mode: parity with the old static pickers, zero cost
# ---------------------------------------------------------------------------

def _ladder_pick(k_pad, n_pad, b, budget):
    """The retired fixed-ladder _pick_tn (pre-PR6 cvmm.py) for parity."""
    for tn in (512, 384, 256, 128):
        if n_pad % tn == 0 and \
                autotune.ws_matmul_tile(k_pad, tn, b) <= budget:
            return tn
    return None


def test_heuristic_matches_old_ladder_on_ladder_shapes(tuner):
    budget = cvmm.VMEM_BUDGET
    for n_pad in (128, 256, 384, 512):
        for k_pad in (128, 256, 640):
            for b in (2, 4):
                assert autotune.pick_tn(k_pad, n_pad, b, budget=budget) == \
                    _ladder_pick(k_pad, n_pad, b, budget), (k_pad, n_pad, b)


def test_divisibility_fix_n640(tuner):
    # the old ladder collapsed n_pad=640 (divisible by 128 but by neither
    # 384 nor 512) to tn=128; the enumeration finds the full-width tile
    assert autotune.pick_tn(128, 640, 4, budget=cvmm.VMEM_BUDGET) == 640
    assert _ladder_pick(128, 640, 4, cvmm.VMEM_BUDGET) == 128  # the old miss
    # under a budget too small for 640, the next dividing LANE multiple wins
    # (for 640 that is 128: 256/384/512 don't divide it)
    small = autotune.ws_matmul_tile(128, 128, 4)
    assert autotune.pick_tn(128, 640, 4, budget=small) == 128


def test_heuristic_no_io_no_bench(tuner):
    autotune.pick_tn(128, 512, 4, budget=cvmm.VMEM_BUDGET)
    autotune.fused_w1_tiles(128, 512, 4, 2, 3, budget=cvmm.VMEM_BUDGET)
    autotune.streamed_dw_tiles(128, 512, 4, budget=cvmm.VMEM_BUDGET)
    autotune.gather_tiles(128, 4, budget=cvmm.VMEM_BUDGET)
    assert autotune.STATS["microbench_calls"] == 0
    assert autotune.STATS["tuned"] == 0
    assert list(tuner.iterdir()) == []          # cache dir never touched


def test_heuristic_provenance_and_none(tuner):
    d = autotune.fused_w1_tiles(128, 512, 4, 2, 3, budget=cvmm.VMEM_BUDGET)
    assert d.provenance == "heuristic"
    assert d.tiles["tn"] == 512 and d.tiles["n_buffers"] == 2
    assert autotune.decide("pick_tn",
                           {"k_pad": 128, "n_pad": 512, "b": 4},
                           budget=1 << 10) == (None, "none")


# ---------------------------------------------------------------------------
# VMEM budget single-sourcing
# ---------------------------------------------------------------------------

def test_budget_from_hardware_model(tuner):
    hw = analysis.hardware_for("tpu", "TPU v5 lite")
    assert autotune.default_vmem_budget(hw) == \
        int(hw.vmem_bytes * autotune.KERNEL_VMEM_FRACTION)
    # cvmm's module-level budget comes from the same derivation (12 MiB for
    # the 16 MiB/core models)
    assert cvmm.VMEM_BUDGET == 12 * 2**20 == autotune.default_vmem_budget()


def test_hardware_model_from_device_kind():
    assert analysis.hardware_for("tpu", "TPU v5 lite") is analysis.V5E
    assert analysis.hardware_for("cpu") is analysis.CPU_INTERPRET
    # another TPU generation or an unknown backend is an error, never v5e
    with pytest.raises(ValueError, match="TPU kind"):
        analysis.hardware_for("tpu", "TPU v4")
    with pytest.raises(ValueError, match="backend"):
        analysis.hardware_for("metal")


def test_budget_env_override(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "65536")
    assert autotune.default_vmem_budget() == 65536
    # decide() with no explicit budget picks up the override: nothing fits
    # 64 KiB at these shapes
    assert autotune.decide(
        "pick_tn", {"k_pad": 128, "n_pad": 512, "b": 4}).tiles is None


# ---------------------------------------------------------------------------
# Tuned mode + cache lifecycle
# ---------------------------------------------------------------------------

def test_tuned_winner_from_microbench(tuner):
    autotune.enable(True)
    calls = []
    # fake timings invert the heuristic preference: smallest tile "fastest"
    autotune.set_benchmark_override(_spy(calls, time_of=lambda t: t["tn"]))
    d = autotune.decide("pick_tn", {"k_pad": 128, "n_pad": 512, "b": 4},
                        budget=cvmm.VMEM_BUDGET)
    assert d == ({"tm": 128, "tn": 128}, "tuned")
    assert len(calls) == autotune.STATS["microbench_calls"] == \
        autotune.TUNE_TOP_K
    assert {c[2]["tn"] for c in calls} == {512, 256, 128}


def test_cache_hit_skips_microbench(tuner):
    autotune.enable(True)
    calls = []
    autotune.set_benchmark_override(_spy(calls))
    dims = {"k_pad": 128, "n_pad": 512, "b": 4}
    first = autotune.decide("pick_tn", dims, budget=cvmm.VMEM_BUDGET)
    n_bench = autotune.STATS["microbench_calls"]
    assert n_bench > 0 and first.provenance == "tuned"

    # fresh "process": drop the in-memory mirror, keep the on-disk file
    autotune.reset(memory_only=True)
    again = autotune.decide("pick_tn", dims, budget=cvmm.VMEM_BUDGET)
    assert again == first
    assert autotune.STATS["microbench_calls"] == n_bench   # zero new runs
    assert autotune.STATS["cache_hits"] >= 1


def test_cache_file_schema_and_atomic_publish(tuner):
    autotune.enable(True)
    autotune.set_benchmark_override(_spy([]))
    autotune.decide("pick_tn", {"k_pad": 128, "n_pad": 512, "b": 4},
                    budget=cvmm.VMEM_BUDGET)
    path = autotune.cache_path()
    data = json.load(open(path))
    assert data["schema"] == autotune.SCHEMA_VERSION
    assert "pick_tn|b=4|k_pad=128|n_pad=512" in data["entries"]
    entry = data["entries"]["pick_tn|b=4|k_pad=128|n_pad=512"]
    assert entry["provenance"] == "tuned" and "tiles" in entry
    # atomic publish: no .tune-* temp files survive a successful store
    leftovers = [f for f in os.listdir(os.path.dirname(path))
                 if f.startswith(".tune-")]
    assert leftovers == []


@pytest.mark.parametrize("payload", [
    "{ not json at all",                               # corrupt
    json.dumps({"schema": 999, "entries": {}}),        # future schema
    json.dumps({"schema": autotune.SCHEMA_VERSION}),   # missing entries
    json.dumps([1, 2, 3]),                             # wrong type
])
def test_invalid_cache_discarded_and_rebuilt(tuner, payload):
    autotune.enable(True)
    autotune.set_benchmark_override(_spy([]))
    path = autotune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(payload)
    d = autotune.decide("pick_tn", {"k_pad": 128, "n_pad": 512, "b": 4},
                        budget=cvmm.VMEM_BUDGET)
    assert d.tiles is not None                   # never raises, still tunes
    assert autotune.STATS["cache_invalid"] >= 1
    rebuilt = json.load(open(path))              # file is valid again
    assert rebuilt["schema"] == autotune.SCHEMA_VERSION
    assert len(rebuilt["entries"]) == 1


def test_concurrent_writers_merge(tuner):
    autotune.enable(True)
    autotune.set_benchmark_override(_spy([]))
    d1 = {"k_pad": 128, "n_pad": 512, "b": 4}
    d2 = {"k_pad": 128, "n_pad": 256, "b": 4}
    autotune.decide("pick_tn", d1, budget=cvmm.VMEM_BUDGET)
    # second writer starts cold (no memory mirror), tunes a different key:
    # its read-merge-write must preserve the first writer's entry
    autotune.reset(memory_only=True)
    autotune.decide("pick_tn", d2, budget=cvmm.VMEM_BUDGET)
    entries = json.load(open(autotune.cache_path()))["entries"]
    assert {"pick_tn|b=4|k_pad=128|n_pad=512",
            "pick_tn|b=4|k_pad=128|n_pad=256"} <= set(entries)


def test_shrunk_budget_invalidates_cached_tiles(tuner):
    autotune.enable(True)
    calls = []
    autotune.set_benchmark_override(_spy(calls))
    dims = {"k_pad": 128, "n_pad": 512, "b": 4}
    big = autotune.decide("pick_tn", dims, budget=cvmm.VMEM_BUDGET)
    assert big.tiles["tn"] == 512                # constant spy -> roofline/
    autotune.reset(memory_only=True)             # heuristic order wins
    # a budget only tn=128 fits under: the cached 512 is no longer legal and
    # must NOT be honored
    small = autotune.ws_matmul_tile(128, 128, 4)
    d = autotune.decide("pick_tn", dims, budget=small)
    assert d == ({"tm": 128, "tn": 128}, "tuned")


def test_tuned_enumerates_pipeline_depths(tuner):
    autotune.enable(True)
    calls = []
    # deeper pipeline "faster": tuner should land on n_buffers=3
    autotune.set_benchmark_override(
        _spy(calls, time_of=lambda t: -t["n_buffers"]))
    d = autotune.fused_w1_tiles(128, 512, 4, 2, 3, budget=cvmm.VMEM_BUDGET)
    assert d.provenance == "tuned" and d.tiles["n_buffers"] == 3
    # while the heuristic (disabled) stays at the depth-2 default
    autotune.enable(False)
    h = autotune.fused_w1_tiles(128, 512, 4, 2, 3, budget=cvmm.VMEM_BUDGET)
    assert h == (dict(h.tiles), "heuristic") and h.tiles["n_buffers"] == 2


# ---------------------------------------------------------------------------
# ops-layer integration: one tile plan per call site, budget threaded
# ---------------------------------------------------------------------------

def test_ops_tile_plans_heuristic(tuner):
    fused = ops.fused_mlp_tiles(128, 512, glu=True)
    assert fused is not None and fused.provenance == "heuristic"
    assert (fused.w1_tn, fused.w2_tn, fused.dw_tb) == (512, 128, 512)
    planned = ops.planned_call_tiles(128, 512)
    assert planned is not None and planned.provenance == "heuristic"
    assert (planned.fwd_tn, planned.dx_tn) == (512, 128)
    assert autotune.STATS["microbench_calls"] == 0


def test_ops_tile_plans_respect_budget(tuner, monkeypatch):
    monkeypatch.setattr(cvmm, "VMEM_BUDGET", 1 << 10)
    assert ops.fused_mlp_tiles(128, 512, glu=True) is None
    assert ops.planned_call_tiles(128, 512) is None
    kplan = ops.plan_sort_kernels("pallas_fused", 128, 512, "relu",
                                  glu=True)
    assert kplan.rung == "ragged"


def test_gather_decision_and_fits(tuner):
    d = autotune.gather_tiles(128, 4, budget=cvmm.VMEM_BUDGET)
    assert d.tiles == {"tm": 128, "n_buffers": 2}
    assert autotune.gather_fits(128, 4, budget=cvmm.VMEM_BUDGET)
    assert not autotune.gather_fits(128, 4, budget=1 << 10)
