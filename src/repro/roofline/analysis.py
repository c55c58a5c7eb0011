"""Three-term roofline from a compiled dry-run artifact.

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_wire_bytes_per_device / ICI_bw

Hardware model: TPU v5e -- 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI (we use
one link-equivalent per chip; multi-link meshes scale this linearly). The HLO module
is post-SPMD, so all quantities are already per-device. The cross-pod 'pod' axis is
DCN (~6.25 GB/s/host effective); collectives whose replica groups span pods are the
multi-pod dry-run's concern and appear in coll_by_kind.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from .hlo_analysis import analyze_hlo_text


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float            # bf16 FLOP/s per chip
    hbm_bw: float                # B/s per chip
    ici_bw: float                # B/s per link per chip
    hbm_bytes: float             # capacity per chip
    # Fast on-chip tile memory per core (VMEM on TPU). The kernel autotuner
    # (kernels/autotune.py) slices its per-kernel working-set budget from this
    # instead of hard-coding bytes; off-TPU models mirror the TPU value so
    # interpret-mode tile choices match the TPU defaults bit-for-bit.
    vmem_bytes: float = 16 * 2**20


V5E = Hardware(name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
               hbm_bytes=16e9)

# Interpret-mode stand-in for CPU CI runs: throughput numbers only order the
# autotuner's roofline pruning (relative cost), they are not calibrated.
CPU_INTERPRET = Hardware(name="cpu_interpret", peak_flops=2e11, hbm_bw=4e10,
                         ici_bw=1e9, hbm_bytes=32e9)

# Coarse A100-class placeholder so gpu backends get a sane pruning model.
GPU_GENERIC = Hardware(name="gpu_generic", peak_flops=312e12, hbm_bw=2.0e12,
                       ici_bw=300e9, hbm_bytes=80e9)

# jax device_kind -> model, for the TPU generations with a peak table here.
TPU_MODELS = {"TPU v5 lite": V5E}

# One model per jax backend the tuner can meet (the VMEM analyzer sweeps
# these); a TPU's model is looked up by its device kind in TPU_MODELS.
HARDWARE_MODELS = {"tpu": V5E, "cpu": CPU_INTERPRET, "gpu": GPU_GENERIC}


def hardware_for(backend: str, device_kind: str = "") -> Hardware:
    """Hardware model for a jax backend and device kind (kernels/autotune.py
    reads both from ``jax.devices()[0]``; this module stays importable
    without jax). Raises for a backend or TPU kind with no entry rather than
    guessing another chip's peaks and VMEM."""
    if backend == "tpu":
        if device_kind not in TPU_MODELS:
            raise ValueError(f"no hardware model for TPU kind {device_kind!r};"
                             f" known: {sorted(TPU_MODELS)}")
        return TPU_MODELS[device_kind]
    if backend not in HARDWARE_MODELS:
        raise ValueError(f"no hardware model for backend {backend!r}")
    return HARDWARE_MODELS[backend]


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    # per-device seconds
    compute_s: float
    memory_s: float
    collective_s: float
    # raw per-device quantities
    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_by_kind: Dict[str, float]
    # usefulness
    model_flops_global: float
    hlo_flops_global: float
    # memory fit
    memory_analysis: Dict[str, float]
    # xla cross-check (body-once semantics)
    xla_cost_analysis: Dict[str, float]

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Optimistic (perfect-overlap) lower bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops_global / max(self.hlo_flops_global, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak the USEFUL model flops achieve at the bound step time."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return (self.model_flops_global / self.n_chips / t) / V5E.peak_flops

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.update(bound=self.bound, step_time_s=self.step_time_s,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def model_flops(cfg, shape, n_tokens: Optional[int] = None) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); D = tokens processed.
    Train counts fwd+bwd (the 6x); decode/prefill use 2*N*D (fwd only)."""
    pc = cfg.param_counts()
    n_active = pc["active"]
    if n_tokens is None:
        if shape.mode == "decode":
            n_tokens = shape.global_batch            # one token per sequence
        else:
            n_tokens = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.mode == "train" else 2.0
    return mult * n_active * n_tokens


def analyze_compiled(compiled, *, arch: str, shape, mesh_name: str, n_chips: int,
                     cfg, hw: Hardware = V5E) -> RooflineReport:
    text = compiled.as_text()
    cost = analyze_hlo_text(text)
    try:
        ca = compiled.cost_analysis()
        ca = ca if isinstance(ca, dict) else ca[0]
        xla_ca = {k: float(v) for k, v in ca.items()
                  if k in ("flops", "bytes accessed")}
    except Exception:
        xla_ca = {}
    try:
        ma = compiled.memory_analysis()
        mem = {k: float(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")}
        mem["total_hbm_bytes"] = (mem["argument_size_in_bytes"]
                                  + mem["output_size_in_bytes"]
                                  + mem["temp_size_in_bytes"]
                                  - mem["alias_size_in_bytes"])
    except Exception:
        mem = {}

    mf = model_flops(cfg, shape)
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, n_chips=n_chips,
        compute_s=cost.flops / hw.peak_flops,
        memory_s=cost.hbm_bytes / hw.hbm_bw,
        collective_s=cost.coll_wire_bytes / hw.ici_bw,
        flops=cost.flops, hbm_bytes=cost.hbm_bytes,
        coll_bytes=cost.coll_wire_bytes, coll_by_kind=dict(cost.coll_by_kind),
        model_flops_global=mf, hlo_flops_global=cost.flops * n_chips,
        memory_analysis=mem, xla_cost_analysis=xla_ca)
