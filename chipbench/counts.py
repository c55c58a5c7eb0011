"""Operations and bytes from shapes, and the chip's peaks.

Counts are what the algorithm needs, not what a kernel happens to pad to: a
dropless sigma-MoE layer over N tokens computes N*k routed rows. So a
roofline share read against them cannot pass 100% because of padding the
program chose. Element sizes are bfloat16 (2 bytes) for activations and
weights; gradients of the weights accumulate in float32 (4 bytes).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
BF16, F32 = 2, 4


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def ideal_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------- kernels

def grouped_gemm(rows: int, k_in: int, n_out: int, experts: int,
                 outputs: int = 1) -> Tuple[float, float]:
    """rows routed (token, expert) rows times their experts' (k_in, n_out)
    matrices: read the rows and every expert's matrix once, write
    ``outputs`` results per row (2 with saved pre-activations)."""
    flops = 2.0 * rows * k_in * n_out
    nbytes = BF16 * (rows * k_in + experts * k_in * n_out
                     + outputs * rows * n_out)
    return flops, nbytes


def grouped_dw(rows: int, k_in: int, n_out: int,
               experts: int) -> Tuple[float, float]:
    """Weight gradient of a grouped GEMM: both row operands read once,
    each expert's (k_in, n_out) gradient written in float32."""
    flops = 2.0 * rows * k_in * n_out
    nbytes = BF16 * rows * (k_in + n_out) + F32 * experts * k_in * n_out
    return flops, nbytes


def row_gather(rows: int, width: int) -> Tuple[float, float]:
    return 0.0, 2.0 * BF16 * rows * width


# ----------------------------------------------------------------- models

def attention_proj_params(m) -> int:
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    return m["d_model"] * (2 * q + 2 * kv)


def moe_active_params(m) -> int:
    per = (3 if m["glu_experts"] else 2) * m["d_model"] * m["expert_size"]
    return m["k"] * per + m["d_model"] * m["n_experts"]


def forward_flops_per_token(m, keys: float) -> float:
    """Forward matrix-product FLOPs of one token attending ``keys`` keys on
    average in every layer, plus the output head (embedding lookup free)."""
    per_layer = 2.0 * (attention_proj_params(m) + moe_active_params(m))
    per_layer += (3 if m.get("attention_kind") == "xl_rel" else 2) \
        * 2.0 * m["n_heads"] * m["head_dim"] * keys
    return m["n_layers"] * per_layer + 2.0 * m["d_model"] * m["vocab_size"]


def train_flops_per_token(m, seq: int) -> float:
    """Forward + backward (3x forward) per token of a segment of ``seq``
    tokens after ``xl_memory`` remembered ones; recomputation not counted.
    xl_rel attention scores both the content and the position term."""
    keys = m.get("xl_memory", 0) + (seq + 1) / 2.0
    return 3.0 * forward_flops_per_token(m, keys)

