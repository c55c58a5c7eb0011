"""Pass 3: VMEM-budget prover for every tile the autotuner can emit.

The tile pickers in ``kernels/autotune.py`` are the only thing standing
between a kernel launch and a Mosaic "scoped memory exceeded" crash — or
worse, a tuned cache entry that fit under yesterday's budget and silently
busts today's. This pass closes the loop offline: it enumerates EVERY
candidate every family's enumerator can produce (``enumerate_candidates``,
both the disabled-tuner heuristic space and the full tuned space — the cache
layer only ever honors entries that are still members of that list, so this
sweep covers every tile ``decide()`` can return) and proves each one fits
``default_vmem_budget()`` for every hardware model in
``roofline.analysis.HARDWARE_MODELS``.

The fit proof uses an INDEPENDENT working-set model: ``launch_inventory``
itemizes the VMEM-resident buffers of each kernel launch straight from the
``scratch_shapes``/BlockSpec shapes in ``kernels/cvmm.py`` (each entry below
cites its launch). The tuner's closed-form ``ws_*`` formulas are then
cross-checked against the itemized sum ("formula-drift"): if someone grows a
kernel's scratch or adds an output block without updating the tuner's
accounting, the two models disagree and the pass fails — before the
undersized budget check ever lets a busting tile through.

Accounting conventions (shared with the tuner; the drift check enforces
them): manually-managed gather scratch is exact; Mosaic-pipelined blocked
operands/outputs of the streamed kernels count 2x (revolving buffers); the
plain blocked GEMM counts single-buffered, its pipelining headroom is what
``KERNEL_VMEM_FRACTION`` leaves free.

``prefetch_inventory`` itemizes the other memory a launch holds, its
scalar-prefetch operands in SMEM: the plan's tables and, on every launch of
the train path, the live bound ``n_live`` (kernels/cvmm.py, "Live bound").
The bound adds no VMEM buffer: a slack step reuses the last live step's
blocks. The prefetch check traces each kernel at a small grid and proves
its scalar operands are the itemized ones ("prefetch-drift").

The threading check then resolves real ``ops.fused_mlp_tiles`` /
``ops.planned_call_tiles`` / ``ops.plan_sort_kernels`` decisions over a shape
grid and proves every (width, depth) pair a launch actually binds is itself a
member of that launch's candidate list — the invariant that caught the fused
w1 training launch borrowing the inference decision's pipeline depth (fixed
by giving ``FusedTiles`` a ``w1_train_nb``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from ..kernels import autotune
from ..roofline.analysis import HARDWARE_MODELS
from .report import Finding

TM = autotune.TM
LANE = autotune.LANE


def _bad(check: str, location: str, detail: str) -> Finding:
    return Finding("vmem", check, location, detail)


# ---------------------------------------------------------------------------
# Independent launch inventory (shapes cited from kernels/cvmm.py)
# ---------------------------------------------------------------------------

def launch_inventory(family: str, dims: Dict[str, int],
                     tiles: Dict[str, int]) -> List[Tuple[str, int]]:
    """Itemized VMEM-resident buffers of one kernel launch: [(what, bytes)].

    Derived from the pallas_call scratch_shapes and BlockSpec block shapes,
    NOT from the tuner's formulas — the drift check compares the two."""
    b = dims["b"]
    if family in ("pick_tn", "decode_gemm"):
        # cvmm_pallas / cvmm_fused_w2_pallas: x block (TM, K), weight block
        # (1, K, tn), f32 accumulator-sized output block (TM, tn). The
        # decode_gemm shape-class launches the SAME kernel (ops.DecodePlan's
        # grouped GEMMs), so the per-step inventory is identical — only the
        # cost model and reference pass differ.
        k, tn = dims["k_pad"], tiles["tn"]
        return [("x block (TM,K)", TM * k * b),
                ("w block (1,K,tn)", k * tn * b),
                ("out block (TM,tn) f32", TM * tn * 4)]
    if family == "fused_w1":
        # cvmm_fused_w1_pallas: scratch pltpu.VMEM((n_buffers, TM, K)),
        # n_weights weight blocks (1, K, tn), n_out output blocks (TM, tn)
        # kept in f32-width accumulators; blocked refs pipelined 2x.
        k, tn = dims["k_pad"], tiles["tn"]
        nb = tiles["n_buffers"]
        nw, no = dims["n_weights"], dims["n_out"]
        return [("gather scratch (nb,TM,K)", nb * TM * k * b),
                ("w blocks (1,K,tn) x2", 2 * nw * k * tn * b),
                ("out blocks (TM,tn) x2", 2 * no * TM * tn * max(b, 4))]
    if family == "streamed_dw":
        # cvmm_dw_streamed_pallas: scratch pltpu.VMEM((n_buffers, TM, W_s)),
        # blocked operand (TM, tb), f32 output block (1, K, tb)/(1, tb, N) —
        # W_stream * tb either way; blocked refs pipelined 2x.
        sw, tb = dims["stream_w"], tiles["tb"]
        nb = tiles["n_buffers"]
        return [("gather scratch (nb,TM,Ws)", nb * TM * sw * b),
                ("operand block (TM,tb) x2", 2 * TM * tb * b),
                ("dW block (Ws,tb) f32 x2", 2 * sw * tb * 4)]
    if family in ("gather", "gather_dedup"):
        # cvmm_gather_rows_pallas: scratch pltpu.VMEM((n_buffers, TM, K)),
        # output block (TM, K) pipelined 2x.
        k = dims["k_pad"]
        nb = tiles["n_buffers"]
        return [("gather scratch (nb,TM,K)", nb * TM * k * b),
                ("out block (TM,K) x2", 2 * TM * k * b)]
    raise ValueError(f"unknown kernel family {family!r}")


# Scalar-prefetch operands of each family's launches, in call order.
_RUNS = ("row_src", "run_start", "run_off")
_PREFETCH = {
    # cvmm_pallas, cvmm_fused_w2_pallas and the decode GEMMs (the same
    # kernel): the expert of each row tile, the live bound
    "pick_tn": ("tile_expert", "n_live"),
    "decode_gemm": ("tile_expert", "n_live"),
    "fused_w1": _RUNS + ("tile_expert", "n_live"),
    "streamed_dw": _RUNS + ("tile_expert", "n_live"),
    "gather": _RUNS,                         # PKM / top-k: no live bound
    "gather_dedup": _RUNS,
}


def prefetch_inventory(family: str, m_tiles: int) -> List[Tuple[str, int]]:
    """Itemized scalar-prefetch (SMEM) operands of one launch over
    ``m_tiles`` row tiles: [(what, int32 elements)]."""
    from ..kernels import cvmm
    size = {"row_src": m_tiles * TM, "run_start": m_tiles * TM,
            "run_off": m_tiles * (len(cvmm._RUN_SIZES) + 1),
            "tile_expert": m_tiles, "n_live": 1}
    return [(name, size[name]) for name in _PREFETCH[family]]


def _traced_prefetch(family: str, m_tiles: int) -> List[int]:
    """Element counts of the scalar-prefetch operands of the family's
    pallas_calls as kernels/cvmm.py builds them (traced, not run)."""
    import jax
    import jax.numpy as jnp
    from ..kernels import cvmm

    m_pad, n = m_tiles * TM, 40
    x = jnp.zeros((n, LANE), jnp.float32)
    rows = jnp.zeros((m_pad, LANE), jnp.float32)
    ints = jnp.zeros((m_pad,), jnp.int32)
    run_off = jnp.zeros((m_tiles * (len(cvmm._RUN_SIZES) + 1),), jnp.int32)
    te = jnp.zeros((m_tiles,), jnp.int32)
    w = jnp.zeros((2, LANE, LANE), jnp.float32)
    calls = {
        "pick_tn": [lambda: cvmm.cvmm_pallas(rows, te, w, interpret=True),
                    lambda: cvmm.cvmm_fused_w2_pallas(
                        rows, te, w, jnp.zeros((m_tiles, TM)),
                        interpret=True)],
        "fused_w1": [lambda: cvmm.cvmm_fused_w1_pallas(
            x, ints, ints, run_off, te, w, None, act_name="relu",
            interpret=True)],
        "streamed_dw": [lambda: cvmm.cvmm_dw_streamed_pallas(
            x, rows, ints, ints, run_off, te, 2, stream_x=True,
            interpret=True)],
        "gather": [lambda: cvmm.cvmm_gather_rows_pallas(
            x, ints, ints, run_off, interpret=True)],
    }[family]
    found = []
    for call in calls:
        for eqn in jax.make_jaxpr(call)().eqns:
            if eqn.primitive.name == "pallas_call":
                k = eqn.params["grid_mapping"].num_index_operands
                found.append([int(v.aval.size) for v in eqn.invars[:k]])
    return found


def _check_prefetch():
    findings: List[Finding] = []
    checks = 0
    m_tiles = 3
    for family in ("pick_tn", "fused_w1", "streamed_dw", "gather"):
        want = [n for _, n in prefetch_inventory(family, m_tiles)]
        for got in _traced_prefetch(family, m_tiles):
            checks += 1
            if got != want:
                findings.append(_bad(
                    "prefetch-drift", f"{family} m_tiles={m_tiles}",
                    f"the launch prefetches scalar operands of {got} "
                    f"elements, the inventory itemizes {want} "
                    f"({list(_PREFETCH[family])})"))
    return findings, checks


def launch_bytes(family: str, dims: Dict[str, int],
                 tiles: Dict[str, int]) -> int:
    return sum(n for _, n in launch_inventory(family, dims, tiles))


def tuner_bytes(family: str, dims: Dict[str, int],
                tiles: Dict[str, int]) -> int:
    """The tuner's own closed-form working set for the same launch."""
    b = dims["b"]
    if family in ("pick_tn", "decode_gemm"):
        return autotune.ws_matmul_tile(dims["k_pad"], tiles["tn"], b)
    if family == "fused_w1":
        return autotune.ws_fused_w1(dims["k_pad"], tiles["tn"], b,
                                    dims["n_weights"], dims["n_out"],
                                    tiles["n_buffers"])
    if family == "streamed_dw":
        return autotune.ws_streamed_dw(dims["stream_w"], tiles["tb"], b,
                                       tiles["n_buffers"])
    return autotune.ws_gather(dims["k_pad"], b, tiles["n_buffers"])


# ---------------------------------------------------------------------------
# Shape grids: the padded dims production code can key the tuner with
# ---------------------------------------------------------------------------

_WIDTHS = (128, 256, 512, 640, 1024, 2048, 4096)


def _dims_grid(family: str):
    if family == "pick_tn":
        return [{"k_pad": k, "n_pad": n, "b": b}
                for k in (128, 512, 1024, 4096) for n in _WIDTHS
                for b in (2, 4)]
    if family == "decode_gemm":
        # Decode GEMMs key on (d_pad, g_pad) pairs of real expert MLPs — a
        # smaller grid than pick_tn's training sweep, but both orientations
        # (w1: d->g, w2: g->d) of each shape are covered.
        return [{"k_pad": k, "n_pad": n, "b": b}
                for k in (128, 512, 1024) for n in (128, 512, 640, 1024)
                for b in (2, 4)]
    if family == "fused_w1":
        return [{"k_pad": k, "n_pad": n, "b": b, "n_weights": nw,
                 "n_out": no}
                for k in (128, 512, 1024) for n in _WIDTHS for b in (2, 4)
                for nw in (1, 2) for no in (1, 2, 3)]
    if family == "streamed_dw":
        return [{"stream_w": sw, "block_w": bw, "b": b}
                for sw in (128, 512, 1024, 4096) for bw in _WIDTHS
                for b in (2, 4)]
    return [{"k_pad": k, "b": b} for k in _WIDTHS + (8192,)
            for b in (1, 2, 4)]


def _width_key(family: str) -> str:
    return "tb" if family == "streamed_dw" else "tn"


def _min_tiles(family: str, dims: Dict[str, int]) -> Dict[str, int]:
    """The smallest candidate the enumerator could ever offer."""
    t = {"tm": TM, _width_key(family): LANE, "n_buffers": 2}
    if family in ("pick_tn", "decode_gemm"):
        del t["n_buffers"]
    if family in ("gather", "gather_dedup"):
        del t[_width_key(family)]
    return t


def _check_candidate_space(budget: int, where: str):
    findings: List[Finding] = []
    checks = 0
    for family in autotune.families():
        wk = _width_key(family)
        depths = autotune.FAMILY_DEPTHS[family]
        for dims in _dims_grid(family):
            dimtag = ",".join(f"{k}={v}" for k, v in sorted(dims.items()))
            for tuned in (False, True):
                loc = (f"{family}[{dimtag}] {where}"
                       + (" tuned" if tuned else ""))
                cands = autotune.enumerate_candidates(family, dims,
                                                      budget=budget,
                                                      tuned=tuned)
                for c in cands:
                    checks += 5
                    ws = launch_bytes(family, dims, c)
                    if ws > budget:
                        findings.append(_bad(
                            "budget", loc,
                            f"candidate {c} needs {ws} bytes of VMEM, budget "
                            f"is {budget} — this tile would crash or spill "
                            f"at launch"))
                    tws = tuner_bytes(family, dims, c)
                    if tws != ws:
                        findings.append(_bad(
                            "formula-drift", loc,
                            f"tuner accounts {tws} bytes for {c}, the launch "
                            f"inventory sums to {ws} — the ws_* formula and "
                            f"the kernel's scratch/blocks disagree"))
                    if c.get("tm", TM) != TM:
                        findings.append(_bad(
                            "tm", loc, f"candidate {c} uses tm != {TM}; the "
                            f"plan layout bakes {TM} in"))
                    if wk in c and (c[wk] % LANE
                                    or dims.get("n_pad",
                                                dims.get("block_w",
                                                         c[wk])) % c[wk]):
                        findings.append(_bad(
                            "width", loc,
                            f"candidate width {c[wk]} is not a LANE multiple "
                            f"dividing the padded dim"))
                    nb = c.get("n_buffers")
                    legal = depths if tuned else ((2,) if depths else ())
                    if (nb is None) != (not depths) or \
                            (nb is not None and nb not in legal):
                        findings.append(_bad(
                            "depth", loc,
                            f"candidate depth {nb} is outside FAMILY_DEPTHS"
                            f"[{family!r}] for this tuner mode ({legal})"))
                checks += 1
                if not cands and launch_bytes(
                        family, dims, _min_tiles(family, dims)) <= budget:
                    findings.append(_bad(
                        "needless-degradation", loc,
                        f"no candidates offered although the minimal tile "
                        f"fits the {budget}-byte budget — callers would "
                        f"degrade to the slow path for nothing"))
                if cands and "n_buffers" in cands[0]:
                    checks += 1
                    d0 = min(c["n_buffers"] for c in cands)
                    w0 = max(c[wk] for c in cands
                             if c["n_buffers"] == d0) if wk in cands[0] else \
                        None
                    if cands[0]["n_buffers"] != d0 or \
                            (w0 is not None and cands[0][wk] != w0):
                        findings.append(_bad(
                            "heuristic-order", loc,
                            f"first candidate {cands[0]} is not the "
                            f"shallowest-depth/widest heuristic answer"))
    return findings, checks


# ---------------------------------------------------------------------------
# Tile threading: the pairs ops.py actually binds per launch
# ---------------------------------------------------------------------------

_THREAD_SHAPES = ((128, 512), (512, 2048), (1024, 4096), (256, 640))


def _check_threading():
    import jax.numpy as jnp
    from ..kernels import cvmm as cvmm_mod
    from ..kernels import ops

    findings: List[Finding] = []
    checks = 0
    budget = cvmm_mod.VMEM_BUDGET
    for d_model, g in _THREAD_SHAPES:
        for dtype in (jnp.float32, jnp.bfloat16):
            for glu in (False, True):
                b = jnp.dtype(dtype).itemsize
                nw = 2 if glu else 1
                d_pad = -(-d_model // LANE) * LANE
                g_pad = -(-g // LANE) * LANE
                loc = (f"fused d={d_model} g={g} b={b}"
                       + (" glu" if glu else ""))
                t = ops.fused_mlp_tiles(d_model, g, dtype, glu)
                if t is None:
                    continue
                # Every launch in ops._fused_fwd_impl/_fused_bwd, as the
                # (family, dims, width, depth) it binds. Each pair must be a
                # member of its own launch's tuned candidate list — i.e. a
                # combination some single tuner decision proved fits.
                launches = [
                    ("fused_w1", {"k_pad": d_pad, "n_pad": g_pad, "b": b,
                                  "n_weights": nw, "n_out": 1},
                     {"tm": TM, "tn": t.w1_tn, "n_buffers": t.w1_nb}),
                    ("fused_w1", {"k_pad": d_pad, "n_pad": g_pad, "b": b,
                                  "n_weights": nw, "n_out": 1 + nw},
                     {"tm": TM, "tn": t.w1_train_tn,
                      "n_buffers": t.w1_train_nb}),
                    ("fused_w1", {"k_pad": d_pad, "n_pad": g_pad, "b": b,
                                  "n_weights": 1, "n_out": 1},
                     {"tm": TM, "tn": t.t0_tn, "n_buffers": t.t0_nb}),
                    ("pick_tn", {"k_pad": g_pad, "n_pad": d_pad, "b": b},
                     {"tm": TM, "tn": t.w2_tn}),
                    ("streamed_dw", {"stream_w": d_pad, "block_w": g_pad,
                                     "b": b},
                     {"tm": TM, "tb": t.dw_tb, "n_buffers": t.dw_nb}),
                ]
                for family, dims, tiles in launches:
                    checks += 2
                    cands = autotune.enumerate_candidates(family, dims,
                                                          budget=budget,
                                                          tuned=True)
                    if tiles not in cands:
                        findings.append(_bad(
                            "threading", loc,
                            f"{family} launch binds {tiles}, which is not in "
                            f"its own candidate list — a (width, depth) "
                            f"combination no tuner decision proved fits"))
                    ws = launch_bytes(family, dims, tiles)
                    if ws > budget:
                        findings.append(_bad(
                            "threading-budget", loc,
                            f"{family} launch {tiles} needs {ws} bytes, "
                            f"budget {budget}"))
                p = ops.planned_call_tiles(d_model, g, dtype)
                if p is not None:
                    for kp, npad, tn in ((d_pad, g_pad, p.fwd_tn),
                                         (g_pad, d_pad, p.dx_tn),
                                         (TM, d_pad, p.dw_tk),
                                         (TM, g_pad, p.dw_tn)):
                        checks += 1
                        dims = {"k_pad": kp, "n_pad": npad, "b": b}
                        tiles = {"tm": TM, "tn": tn}
                        if tiles not in autotune.enumerate_candidates(
                                "pick_tn", dims, budget=budget):
                            findings.append(_bad(
                                "threading", loc,
                                f"planned GEMM tile {tiles} at {dims} is "
                                f"not a legal candidate"))
    return findings, checks


def check_vmem() -> Tuple[List[Finding], int]:
    findings: List[Finding] = []
    checks = 0
    for backend in sorted(HARDWARE_MODELS):
        hw = HARDWARE_MODELS[backend]
        budget = autotune.default_vmem_budget(hw)
        f, c = _check_candidate_space(budget, hw.name)
        findings += f
        checks += c
    f, c = _check_threading()
    findings, checks = findings + f, checks + c
    f, c = _check_prefetch()
    return findings + f, checks + c
