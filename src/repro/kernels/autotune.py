"""Roofline-driven kernel autotuner with a persistent on-disk tile cache.

Every planned kernel family in cvmm.py (the fused w1 gather, the gate-epilogue
w2 / plain grouped GEMM, the streamed dW outer products, and the streamed row
gather behind ``ops.gathered_weighted_sum``) needs a tile choice whose working
set fits VMEM. This module is the single place those choices come from:

  heuristic (tuning disabled, the default)
      The zero-cost answer: enumerate every legal candidate — all multiples of
      ``LANE`` that divide the padded width and whose working set fits the
      budget, largest first — and take the first. For widths expressible by
      the old fixed (512, 384, 256, 128) ladder this picks the identical tile;
      for widths the ladder missed (e.g. n_pad=640, a multiple of 128 but of
      neither 384 nor 512) it now finds the larger dividing tile instead of
      collapsing to 128. No I/O, no benchmarking: interpret-mode CI behavior
      is byte-identical to the static pickers this replaces.

  tuned (``REPRO_AUTOTUNE=1`` or ``autotune.enable()``; pre-warm with
  ``python -m benchmarks.run --tune``)
      The same legal candidates (tile width x stream pipeline depth) are
      ranked by a roofline cost estimate — HBM bytes moved and MXU FLOPs per
      grid pass against the active ``roofline.analysis.Hardware`` model, plus
      a fixed per-grid-step overhead — the top ``TUNE_TOP_K`` survivors are
      micro-benchmarked once per (kernel, shape-class, dtype, backend) key,
      and the winner is persisted to an on-disk JSON cache. Streamed families
      are measured at a fixed mixed-contiguity routing (``run_class``
      "mixed": half contiguous run-batched chunks, half scattered single-row
      chunks) so the measurement exercises both ends of the DMA chunk-size
      classes.

Cache layout
------------
One JSON file per backend: ``<cache_dir>/<backend>.json`` where ``cache_dir``
is ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune``. Schema::

    {"schema": 1, "backend": "tpu", "hardware": "tpu_v5e",
     "entries": {"<family>|<dim>=<val>|...": {
         "tiles": {"tm": 128, "tn": 512, ...}, "provenance": "tuned",
         "us": 123.4, "estimate_s": ..., "run_class": "mixed"}}}

Keys are the padded shape dims (already LANE-quantized, so they ARE the shape
classes) plus dtype byte width; the backend lives in the filename. Writers
merge with the on-disk state and publish via write-to-temp + atomic
``os.replace`` so concurrent tuners never clobber or tear the file.
Invalidation is graceful: unreadable files, wrong ``schema`` versions, and
malformed entries are discarded and rebuilt, never raised; a cached tile that
is no longer legal under the CURRENT budget (tests shrink it) is ignored and
retuned. ``STATS["microbench_calls"]`` counts real measurements — a warm
cache must re-run with the counter at zero (CI checks this).

The VMEM budget itself is derived here too (``default_vmem_budget``):
``KERNEL_VMEM_FRACTION`` of the active Hardware model's ``vmem_bytes``
(0.75 * 16 MiB = the 12 MiB cvmm.py used to hard-code), overridable via
``$REPRO_VMEM_BUDGET``. kernels/cvmm.py initializes its module-level
``VMEM_BUDGET`` from this and threads it into every query at call time, so
tests that monkeypatch ``cvmm.VMEM_BUDGET`` shrink every picker at once.
"""
from __future__ import annotations

import functools
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from ..roofline.analysis import Hardware, hardware_for

TM = 128            # row tile (MXU-aligned); the CvmmPlan layout bakes this
                    # in, so candidates with any other tm are illegal.
LANE = 128          # lane multiple for K / N tile widths

SCHEMA_VERSION = 1
DEFAULT_CACHE_DIR = "~/.cache/repro/autotune"
KERNEL_VMEM_FRACTION = 0.75   # 12 MiB of the 16 MiB/core VMEM: headroom for
                              # Mosaic's own scratch + scalar memory
TUNE_TOP_K = 3                # candidates surviving the roofline pruning
BENCH_ITERS = 3               # min-of-N timing per surviving candidate
M_REF_TILES = 8               # reference row-tile count for cost + bench
STEP_OVERHEAD_S = 2e-6        # fixed per-grid-step cost in the roofline model

# Stream pipeline depths each family's candidate enumerator may emit when
# tuning is enabled (disabled -> depth 2 only, the static heuristic). This
# table — not the enumerator bodies — is what repro.analysis reads to know
# which (family, depth) pairs need a hazard proof and a VMEM fit proof, so a
# new depth added here is automatically swept by both passes.
FAMILY_DEPTHS: Dict[str, tuple] = {
    "pick_tn": (),                # blocked GEMM: no gather stream
    "decode_gemm": (),            # same kernel, decode (tiny-M) shape-class
    "fused_w1": (2, 3),
    "streamed_dw": (2, 3),
    "gather": (2, 3, 4),          # bare gather is DMA-bound: depth 4 can pay
    "gather_dedup": (2, 3, 4),
}
SUPPORTED_DEPTHS = (2, 3, 4)      # union; every streamed kernel accepts these

STATS = {"microbench_calls": 0, "cache_hits": 0, "tuned": 0,
         "cache_invalid": 0}

_ENABLED: Optional[bool] = None           # None -> read $REPRO_AUTOTUNE
_MEM_CACHE: Dict[str, Dict[str, Any]] = {}  # abs cache path -> loaded file
_BENCH_OVERRIDE: Optional[Callable] = None  # tests inject a fake micro-bench


class TileDecision(NamedTuple):
    tiles: Optional[Dict[str, int]]   # None: no legal candidate fits
    provenance: str                   # "heuristic" | "tuned" | "none"


# ---------------------------------------------------------------------------
# Tuner state knobs
# ---------------------------------------------------------------------------

def enabled() -> bool:
    if _ENABLED is not None:
        return _ENABLED
    return os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0", "false")


def enable(on: Optional[bool] = True) -> None:
    """Force tuning on/off for this process; ``enable(None)`` re-reads the
    ``REPRO_AUTOTUNE`` env var."""
    global _ENABLED
    _ENABLED = on


def reset(*, memory_only: bool = False) -> None:
    """Drop the in-memory cache mirror (tests); optionally keep STATS."""
    _MEM_CACHE.clear()
    if not memory_only:
        for k in STATS:
            STATS[k] = 0


def set_benchmark_override(fn: Optional[Callable]) -> None:
    """Tests: replace the real micro-benchmark with ``fn(family, dims, tiles)
    -> us``. The microbench_calls counter still increments."""
    global _BENCH_OVERRIDE
    _BENCH_OVERRIDE = fn


def active_backend() -> str:
    import jax
    return jax.default_backend()


def active_hardware() -> Hardware:
    import jax
    dev = jax.devices()[0]
    return hardware_for(dev.platform, dev.device_kind)


def default_vmem_budget(hw: Optional[Hardware] = None) -> int:
    """Per-kernel VMEM working-set budget: ``$REPRO_VMEM_BUDGET`` if set, else
    ``KERNEL_VMEM_FRACTION`` of the active Hardware model's capacity."""
    env = os.environ.get("REPRO_VMEM_BUDGET")
    if env:
        return int(env)
    hw = hw if hw is not None else active_hardware()
    return int(hw.vmem_bytes * KERNEL_VMEM_FRACTION)


def cache_path(backend: Optional[str] = None) -> str:
    backend = backend or active_backend()
    root = os.environ.get("REPRO_AUTOTUNE_CACHE") or DEFAULT_CACHE_DIR
    return os.path.join(os.path.expanduser(root), f"{backend}.json")


# ---------------------------------------------------------------------------
# Working-set accounting — the single source of the VMEM fit formulas
# ---------------------------------------------------------------------------

def ws_matmul_tile(k_pad: int, tn: int, bytes_per_el: int) -> int:
    """Blocked grouped-GEMM step (cvmm_pallas / fused w2): one (TM, K) operand
    tile, one (K, tn) weight tile, one (TM, tn) f32 accumulator."""
    return TM * k_pad * bytes_per_el + k_pad * tn * bytes_per_el + TM * tn * 4


def ws_fused_w1(k_pad: int, tn: int, bytes_per_el: int, n_weights: int,
                n_out: int, n_buffers: int = 2) -> int:
    """Streamed gather-fused w1 step: ``n_buffers`` (TM, K) gather scratch
    slots plus weight/output tiles at 2x for Mosaic's pipeline
    double-buffering of blocked operands."""
    scratch = n_buffers * TM * k_pad * bytes_per_el
    return scratch + 2 * (n_weights * k_pad * tn * bytes_per_el
                          + n_out * TM * tn * max(bytes_per_el, 4))


def ws_streamed_dw(stream_w: int, tb: int, bytes_per_el: int,
                   n_buffers: int = 2) -> int:
    """Streamed dW step: gather scratch over the streamed width plus the
    blocked (TM, tb) operand tile and (W_stream, tb) f32 output at 2x."""
    scratch = n_buffers * TM * stream_w * bytes_per_el
    return scratch + 2 * (TM * tb * bytes_per_el + stream_w * tb * 4)


def ws_gather(k_pad: int, bytes_per_el: int, n_buffers: int = 2) -> int:
    """Streamed bare-gather step: scratch slots plus the blocked output tile
    at 2x for pipeline double-buffering."""
    return (n_buffers * TM * k_pad * bytes_per_el
            + 2 * TM * k_pad * bytes_per_el)


def _dividing_widths(n_pad: int) -> List[int]:
    """All multiples of LANE that divide ``n_pad``, largest first — the legal
    tile widths (kernels assert divisibility; Mosaic lanes demand the LANE
    multiple). This is the satellite fix for the old fixed ladder's
    divisibility miss: n_pad=640 yields (640, 128), not just 128."""
    return [t for t in range(n_pad, 0, -LANE) if n_pad % t == 0]


# ---------------------------------------------------------------------------
# Candidate enumeration + roofline cost per kernel family
# ---------------------------------------------------------------------------
# A family spec is (candidates, cost, bench, run_class):
#   candidates(dims, budget) -> ordered [tiles dict, ...]; element 0 is the
#       heuristic answer (largest width, shallowest pipeline).
#   cost(dims, tiles, hw)    -> estimated seconds for a reference pass of
#       M_REF_TILES row tiles (ranking only; absolute value is not claimed).
#   bench(dims, tiles)       -> measured us for the same reference pass.

def _cand_pick_tn(dims, budget):
    k_pad, b = dims["k_pad"], dims["b"]
    return [{"tm": TM, "tn": tn} for tn in _dividing_widths(dims["n_pad"])
            if ws_matmul_tile(k_pad, tn, b) <= budget]


def _cost_pick_tn(dims, tiles, hw):
    k_pad, n_pad, b = dims["k_pad"], dims["n_pad"], dims["b"]
    tn = tiles["tn"]
    m = M_REF_TILES
    steps = m * (n_pad // tn)
    bytes_moved = (m * k_pad * n_pad * b          # weight tile per grid step
                   + m * TM * k_pad * b           # operand tile per m pass
                   + m * TM * n_pad * b)          # output
    flops = 2 * m * TM * k_pad * n_pad
    return max(bytes_moved / hw.hbm_bw, flops / hw.peak_flops) \
        + steps * STEP_OVERHEAD_S


def _cost_decode_gemm(dims, tiles, hw):
    """Decode shape-class: ONE live row tile (a continuous-batching decode
    step routes at most a few hundred rows), so the pass is weight-stream
    bound — the full (K, N) weight panel moves through VMEM for a single
    (TM, K) operand tile and per-step overhead dominates the ranking."""
    k_pad, n_pad, b = dims["k_pad"], dims["n_pad"], dims["b"]
    tn = tiles["tn"]
    steps = n_pad // tn
    bytes_moved = (k_pad * n_pad * b      # the whole weight panel, once
                   + TM * k_pad * b       # one operand tile
                   + TM * n_pad * b)      # one output stripe
    flops = 2 * TM * k_pad * n_pad
    return max(bytes_moved / hw.hbm_bw, flops / hw.peak_flops) \
        + steps * STEP_OVERHEAD_S


def _cand_fused_w1(dims, budget):
    k_pad, b = dims["k_pad"], dims["b"]
    nw, no = dims["n_weights"], dims["n_out"]
    out = []
    for depth in FAMILY_DEPTHS["fused_w1"] if enabled() else (2,):
        out += [{"tm": TM, "tn": tn, "n_buffers": depth}
                for tn in _dividing_widths(dims["n_pad"])
                if ws_fused_w1(k_pad, tn, b, nw, no, depth) <= budget]
    # heuristic order: depth 2 first, widths descending within a depth
    out.sort(key=lambda t: (t["n_buffers"], -t["tn"]))
    return out


def _cost_fused_w1(dims, tiles, hw):
    k_pad, n_pad, b = dims["k_pad"], dims["n_pad"], dims["b"]
    nw, no = dims["n_weights"], dims["n_out"]
    m = M_REF_TILES
    steps = m * (n_pad // tiles["tn"])
    bytes_moved = (m * nw * k_pad * n_pad * b     # weight tiles, re-read per m
                   + m * TM * k_pad * b           # streamed gather rows
                   + no * m * TM * n_pad * b)     # outputs
    flops = 2 * m * TM * k_pad * n_pad * nw
    # deeper pipelines hide more DMA latency behind the MXU: model as a mild
    # discount on the per-step overhead (measurement decides the rest)
    overhead = steps * STEP_OVERHEAD_S * (2.0 / tiles.get("n_buffers", 2))
    return max(bytes_moved / hw.hbm_bw, flops / hw.peak_flops) + overhead


def _cand_streamed_dw(dims, budget):
    sw, b = dims["stream_w"], dims["b"]
    out = []
    for depth in FAMILY_DEPTHS["streamed_dw"] if enabled() else (2,):
        out += [{"tm": TM, "tb": tb, "n_buffers": depth}
                for tb in _dividing_widths(dims["block_w"])
                if ws_streamed_dw(sw, tb, b, depth) <= budget]
    out.sort(key=lambda t: (t["n_buffers"], -t["tb"]))
    return out


def _cost_streamed_dw(dims, tiles, hw):
    sw, bw, b = dims["stream_w"], dims["block_w"], dims["b"]
    tb = tiles["tb"]
    m = M_REF_TILES
    passes = bw // tb
    steps = passes * m
    # the gather stream RESTARTS on every outer pass: larger tb -> fewer
    # re-streams of the whole unsorted operand — the tb-dependent term
    bytes_moved = (passes * m * TM * sw * b       # streamed rows, per pass
                   + m * TM * bw * b              # blocked operand tiles
                   + passes * sw * tb * 4)        # f32 output blocks
    flops = 2 * m * TM * sw * bw
    overhead = steps * STEP_OVERHEAD_S * (2.0 / tiles.get("n_buffers", 2))
    return max(bytes_moved / hw.hbm_bw, flops / hw.peak_flops) + overhead


def _cand_gather(dims, budget):
    k_pad, b = dims["k_pad"], dims["b"]
    depths = FAMILY_DEPTHS["gather"] if enabled() else (2,)
    return [{"tm": TM, "n_buffers": d} for d in depths
            if ws_gather(k_pad, b, d) <= budget]


def _cost_gather(dims, tiles, hw):
    k_pad, b = dims["k_pad"], dims["b"]
    m = M_REF_TILES
    bytes_moved = 2 * m * TM * k_pad * b          # rows in, tile out
    overhead = m * STEP_OVERHEAD_S * (2.0 / tiles.get("n_buffers", 2))
    return bytes_moved / hw.hbm_bw + overhead


def _cost_gather_dedup(dims, tiles, hw):
    """Same streamed gather kernel at the dedup plan's SORTED routing: the
    sorted-unique row space packs ~TM/32-descriptor tiles (blocks of adjacent
    value indices) instead of the mixed plan's ~TM/2, so the per-step
    descriptor overhead — the term the pipeline depth amortizes — is ~1/4 of
    the mixed family's. Byte traffic is identical; the distinct cost shape is
    what makes this a separate cache shape-class."""
    k_pad, b = dims["k_pad"], dims["b"]
    m = M_REF_TILES
    bytes_moved = 2 * m * TM * k_pad * b
    overhead = m * (STEP_OVERHEAD_S / 4) * (2.0 / tiles.get("n_buffers", 2))
    return bytes_moved / hw.hbm_bw + overhead


# ---------------------------------------------------------------------------
# Micro-benchmarks (lazy kernel imports; only run when tuning is enabled)
# ---------------------------------------------------------------------------

def _time_us(fn) -> float:
    import jax
    jax.block_until_ready(fn())                   # compile outside the clock
    best = float("inf")
    for _ in range(BENCH_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _bench_dtype(b: int):
    import jax.numpy as jnp
    return {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32}[b]


def _interpret() -> bool:
    return active_backend() != "tpu"


def _mixed_plan(m_pad: int):
    """Reference gather routing at run_class "mixed": the first half of the
    slots are one contiguous run per tile (large DMA chunk classes), the
    second half stride-2 scattered rows (size-1 chunks) — both ends of the
    run-batched pipeline's chunk-size dispatch get exercised."""
    import jax.numpy as jnp
    import numpy as np
    from . import ops
    half = m_pad // 2
    src = np.empty((m_pad,), np.int32)
    src[:half] = np.arange(half)
    src[half:] = (half + 2 * np.arange(m_pad - half)) % m_pad
    row_src = jnp.asarray(src)
    run_start, _, run_off = ops._plan_runs(row_src, m_pad)
    return row_src, run_start, run_off


def _bench_pick_tn(dims, tiles) -> float:
    import jax
    import jax.numpy as jnp
    from . import cvmm
    dt = _bench_dtype(dims["b"])
    m_pad = M_REF_TILES * TM
    x = jnp.ones((m_pad, dims["k_pad"]), dt)
    te = jnp.zeros((M_REF_TILES,), jnp.int32)
    w = jnp.ones((1, dims["k_pad"], dims["n_pad"]), dt)
    f = jax.jit(functools.partial(cvmm.cvmm_pallas, interpret=_interpret(),
                                  tn=tiles["tn"]))
    return _time_us(lambda: f(x, te, w))


def _bench_decode_gemm(dims, tiles) -> float:
    import jax
    import jax.numpy as jnp
    from . import cvmm
    dt = _bench_dtype(dims["b"])
    x = jnp.ones((TM, dims["k_pad"]), dt)         # one row tile: decode-sized
    te = jnp.zeros((1,), jnp.int32)
    w = jnp.ones((1, dims["k_pad"], dims["n_pad"]), dt)
    f = jax.jit(functools.partial(cvmm.cvmm_pallas, interpret=_interpret(),
                                  tn=tiles["tn"]))
    return _time_us(lambda: f(x, te, w))


def _bench_fused_w1(dims, tiles) -> float:
    import jax
    import jax.numpy as jnp
    from . import cvmm
    dt = _bench_dtype(dims["b"])
    m_pad = M_REF_TILES * TM
    row_src, run_start, run_off = _mixed_plan(m_pad)
    te = jnp.zeros((M_REF_TILES,), jnp.int32)
    x = jnp.ones((m_pad, dims["k_pad"]), dt)
    w1 = jnp.ones((1, dims["k_pad"], dims["n_pad"]), dt)
    glu = dims["n_weights"] == 2
    f = jax.jit(functools.partial(
        cvmm.cvmm_fused_w1_pallas, act_name="relu",
        save_preact=dims["n_out"] > 1, interpret=_interpret(),
        tn=tiles["tn"], n_buffers=tiles["n_buffers"]))
    return _time_us(lambda: f(x, row_src, run_start, run_off, te, w1,
                              w1 if glu else None))


def _bench_streamed_dw(dims, tiles) -> float:
    import jax
    import jax.numpy as jnp
    from . import cvmm
    dt = _bench_dtype(dims["b"])
    m_pad = M_REF_TILES * TM
    row_src, run_start, run_off = _mixed_plan(m_pad)
    te = jnp.zeros((M_REF_TILES,), jnp.int32)
    x = jnp.ones((m_pad, dims["stream_w"]), dt)       # streamed, stays in HBM
    g = jnp.ones((m_pad, dims["block_w"]), dt)        # tile-aligned, blocked
    f = jax.jit(functools.partial(
        cvmm.cvmm_dw_streamed_pallas, n_experts=1, stream_x=True,
        interpret=_interpret(), tb=tiles["tb"], n_buffers=tiles["n_buffers"]))
    return _time_us(lambda: f(x, g, row_src, run_start, run_off, te))


def _bench_gather(dims, tiles) -> float:
    import jax
    import jax.numpy as jnp
    from . import cvmm
    dt = _bench_dtype(dims["b"])
    m_pad = M_REF_TILES * TM
    row_src, run_start, run_off = _mixed_plan(m_pad)
    x = jnp.ones((m_pad, dims["k_pad"]), dt)
    f = jax.jit(functools.partial(cvmm.cvmm_gather_rows_pallas,
                                  interpret=_interpret(),
                                  n_buffers=tiles["n_buffers"]))
    return _time_us(lambda: f(x, row_src, run_start, run_off))


def _sorted_plan(m_pad: int):
    """Reference gather routing at run_class "sorted": ascending row ids in
    32-row blocks separated by gaps — the dedup plan's characteristic layout
    (sorted-unique value indices: dense stretches of co-selected hot rows
    with cold-row gaps between them). Every tile packs into size-32 chunks,
    exercising the large-class end the mixed plan only half-covers. Sources
    span 2*m_pad rows so the gapped pattern stays in bounds."""
    import jax.numpy as jnp
    import numpy as np
    from . import ops
    j = np.arange(m_pad)
    src = (j // 32) * 64 + (j % 32)
    row_src = jnp.asarray(src.astype(np.int32))
    run_start, _, run_off = ops._plan_runs(row_src, 2 * m_pad)
    return row_src, run_start, run_off


def _bench_gather_dedup(dims, tiles) -> float:
    import jax
    import jax.numpy as jnp
    from . import cvmm
    dt = _bench_dtype(dims["b"])
    m_pad = M_REF_TILES * TM
    row_src, run_start, run_off = _sorted_plan(m_pad)
    x = jnp.ones((2 * m_pad, dims["k_pad"]), dt)
    f = jax.jit(functools.partial(cvmm.cvmm_gather_rows_pallas,
                                  interpret=_interpret(),
                                  n_buffers=tiles["n_buffers"]))
    return _time_us(lambda: f(x, row_src, run_start, run_off))


class _Family(NamedTuple):
    candidates: Callable
    cost: Callable
    bench: Callable
    run_class: str


_FAMILIES: Dict[str, _Family] = {
    "pick_tn": _Family(_cand_pick_tn, _cost_pick_tn, _bench_pick_tn, "dense"),
    # Same blocked-GEMM kernel + candidate set as "pick_tn", but costed and
    # measured at ONE row tile — the continuous-batching decode step's tiny-M
    # regime, where training-amortized tile choices stop being representative.
    # A separate shape-class keeps tuned decode winners from overwriting the
    # 24k-token training winners (and vice versa).
    "decode_gemm": _Family(_cand_pick_tn, _cost_decode_gemm,
                           _bench_decode_gemm, "decode"),
    "fused_w1": _Family(_cand_fused_w1, _cost_fused_w1, _bench_fused_w1,
                        "mixed"),
    "streamed_dw": _Family(_cand_streamed_dw, _cost_streamed_dw,
                           _bench_streamed_dw, "mixed"),
    "gather": _Family(_cand_gather, _cost_gather, _bench_gather, "mixed"),
    # Same kernel + candidate set as "gather", but measured/modeled at the
    # dedup plan's sorted-unique routing — a separate shape-class so tuned
    # winners for mixed vs sorted contiguity never overwrite each other.
    "gather_dedup": _Family(_cand_gather, _cost_gather_dedup,
                            _bench_gather_dedup, "sorted"),
}


def families() -> tuple:
    """Every kernel family the tuner can resolve (analysis sweeps these)."""
    return tuple(_FAMILIES)


def enumerate_candidates(family: str, dims: Dict[str, int], *,
                         budget: Optional[int] = None,
                         tuned: bool = True) -> List[Dict[str, int]]:
    """Every tile candidate ``decide(family, dims)`` could ever return — the
    exact list the tuner ranks, heuristic answer first.

    ``tuned=True`` enumerates the full tuning-enabled candidate space (all
    ``FAMILY_DEPTHS`` entries); ``tuned=False`` restricts to what the disabled
    tuner can emit. The VMEM-budget prover (repro.analysis.vmem) walks this
    with an independently derived working-set model: any candidate surviving
    here but busting the budget there is a tile-picker regression caught
    before a kernel ever launches."""
    budget = budget if budget is not None else default_vmem_budget()
    prev = _ENABLED
    enable(tuned)
    try:
        return _FAMILIES[family].candidates(dims, budget)
    finally:
        enable(prev)


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------

def _key(family: str, dims: Dict[str, int]) -> str:
    return family + "|" + "|".join(f"{k}={dims[k]}" for k in sorted(dims))


def _fresh_file(backend: str, hw: Hardware) -> Dict[str, Any]:
    return {"schema": SCHEMA_VERSION, "backend": backend,
            "hardware": hw.name, "entries": {}}


def _valid_file(data) -> bool:
    return (isinstance(data, dict) and data.get("schema") == SCHEMA_VERSION
            and isinstance(data.get("entries"), dict))


def _read_disk(path: str) -> Optional[Dict[str, Any]]:
    """Load + validate the cache file; any corruption or schema drift is
    reported as a miss (STATS["cache_invalid"]) and the file gets rebuilt by
    the next store — never an exception."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except FileNotFoundError:
        return None
    except (OSError, ValueError):
        STATS["cache_invalid"] += 1
        return None
    if not _valid_file(data):
        STATS["cache_invalid"] += 1
        return None
    return data


def _load_cache(path: str) -> Dict[str, Any]:
    if path not in _MEM_CACHE:
        _MEM_CACHE[path] = _read_disk(path) \
            or _fresh_file(active_backend(), active_hardware())
    return _MEM_CACHE[path]


def _store(path: str, key: str, entry: Dict[str, Any]) -> None:
    """Merge-with-disk read-modify-write published via atomic rename:
    concurrent writers each land their own entries; readers never observe a
    torn file."""
    data = _read_disk(path) or _fresh_file(active_backend(),
                                           active_hardware())
    data["entries"][key] = entry
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tune-", suffix=".json")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(data, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _MEM_CACHE[path] = data


def _entry_tiles(entry, candidates) -> Optional[Dict[str, int]]:
    """A cached entry is honored only if its tiles are STILL a legal candidate
    under the current budget (tests shrink budgets; hardware models change)."""
    if not isinstance(entry, dict):
        return None
    tiles = entry.get("tiles")
    if isinstance(tiles, dict) and tiles in candidates:
        return dict(tiles)
    return None


def _measure(family: str, dims: Dict[str, int], tiles: Dict[str, int]) -> float:
    STATS["microbench_calls"] += 1
    fn = _BENCH_OVERRIDE or (lambda f, d, t: _FAMILIES[f].bench(d, t))
    return float(fn(family, dims, tiles))


# ---------------------------------------------------------------------------
# The query
# ---------------------------------------------------------------------------

def decide(family: str, dims: Dict[str, int], *,
           budget: Optional[int] = None) -> TileDecision:
    """Resolve one kernel family's tiles at one shape class.

    Disabled tuner: first legal candidate (the heuristic), zero cost.
    Enabled: cached winner if still legal, else roofline-prune + micro-bench
    the top-k and persist the winner."""
    budget = budget if budget is not None else default_vmem_budget()
    spec = _FAMILIES[family]
    cands = spec.candidates(dims, budget)
    if not cands:
        return TileDecision(None, "none")
    if not enabled():
        return TileDecision(dict(cands[0]), "heuristic")

    path = cache_path()
    key = _key(family, dims)
    cached = _entry_tiles(_load_cache(path)["entries"].get(key), cands)
    if cached is not None:
        STATS["cache_hits"] += 1
        return TileDecision(cached, "tuned")

    hw = active_hardware()
    ranked = sorted(range(len(cands)),
                    key=lambda i: (spec.cost(dims, cands[i], hw), i))
    survivors = [cands[i] for i in ranked[:TUNE_TOP_K]]
    if len(survivors) == 1:
        best, best_us = survivors[0], None
    else:
        best, best_us = survivors[0], float("inf")
        for t in survivors:                     # stable: first strict win
            us = _measure(family, dims, t)
            if us < best_us:
                best, best_us = t, us
    _store(path, key, {
        "tiles": best, "provenance": "tuned", "us": best_us,
        "estimate_s": spec.cost(dims, best, hw), "run_class": spec.run_class})
    STATS["tuned"] += 1
    return TileDecision(dict(best), "tuned")


# Thin per-family views used by kernels/cvmm.py (budget threaded from the
# caller so ``cvmm.VMEM_BUDGET`` monkeypatches shrink everything at once).

def pick_tn(k_pad: int, n_pad: int, bytes_per_el: int, *,
            budget: Optional[int] = None) -> Optional[int]:
    d = decide("pick_tn", {"k_pad": k_pad, "n_pad": n_pad, "b": bytes_per_el},
               budget=budget)
    return None if d.tiles is None else d.tiles["tn"]


def decode_gemm_tiles(k_pad: int, n_pad: int, bytes_per_el: int, *,
                      budget: Optional[int] = None) -> TileDecision:
    """Tile width for the decode-shaped grouped GEMM (ops.DecodePlan): same
    kernel and candidates as ``pick_tn``, separate shape-class so decode
    winners are tuned at tiny-M instead of inheriting training tiles."""
    return decide("decode_gemm", {"k_pad": k_pad, "n_pad": n_pad,
                                  "b": bytes_per_el}, budget=budget)


def fused_w1_tiles(k_pad: int, n_pad: int, bytes_per_el: int, n_weights: int,
                   n_out: int, *, budget: Optional[int] = None) -> TileDecision:
    return decide("fused_w1", {"k_pad": k_pad, "n_pad": n_pad,
                               "b": bytes_per_el, "n_weights": n_weights,
                               "n_out": n_out}, budget=budget)


def streamed_dw_tiles(stream_w: int, block_w: int, bytes_per_el: int, *,
                      budget: Optional[int] = None) -> TileDecision:
    return decide("streamed_dw", {"stream_w": stream_w, "block_w": block_w,
                                  "b": bytes_per_el}, budget=budget)


def gather_tiles(k_pad: int, bytes_per_el: int, *,
                 budget: Optional[int] = None) -> TileDecision:
    return decide("gather", {"k_pad": k_pad, "b": bytes_per_el},
                  budget=budget)


def dedup_gather_tiles(k_pad: int, bytes_per_el: int, *,
                       budget: Optional[int] = None) -> TileDecision:
    """Pipeline depth for the dedup/sorted gather (ops.DedupGatherPlan):
    same kernel and candidates as ``gather_tiles``, separate shape-class —
    the sorted routing's larger chunks shift where extra depth pays."""
    return decide("gather_dedup", {"k_pad": k_pad, "b": bytes_per_el},
                  budget=budget)


def gather_fits(k_pad: int, bytes_per_el: int, n_buffers: int = 2, *,
                budget: Optional[int] = None) -> bool:
    budget = budget if budget is not None else default_vmem_budget()
    return ws_gather(k_pad, bytes_per_el, n_buffers) <= budget
