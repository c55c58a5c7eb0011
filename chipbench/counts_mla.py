"""FLOPs of a train step of the DeepSeek-V3 block as one chip of an
expert-parallel deployment holds it (chipbench/configs/
moonlight-16b-a3b-ep8.json): MLA attention, a dense first layer, and MoE
layers whose held experts compute their share of each token's k routed rows.

Sizes come from the configuration file's ``model`` section; what it does not
name is the model's published constant (``PUBLISHED``, as the configuration's
reference states them). Matrix products count 2 FLOPs a multiply-add;
recomputation is not counted; the embedding lookup is free.
"""
from __future__ import annotations

PUBLISHED = {"router": 64, "kv_lora_rank": 512, "rope_head_dim": 64,
             "v_head_dim": 128, "dense_d_ff": 11264, "first_dense_layers": 1}


def mla_params(m, p=PUBLISHED) -> int:
    d, nh, dqk = m["d_model"], m["n_heads"], m["head_dim"]
    r, dr, dv = p["kv_lora_rank"], p["rope_head_dim"], p["v_head_dim"]
    return (d * nh * dqk + d * (r + dr) + r * nh * (dqk - dr + dv)
            + nh * dv * d)


def active_params(m, p=PUBLISHED) -> float:
    """Weights one token multiplies through, per step's forward: in each MoE
    layer the router, the shared experts and k * held / router of the routed
    experts (the rows routed here)."""
    d, g = m["d_model"], m["expert_size"]
    glu = 3 * d * g
    held = m["k"] * m["n_experts"] / p["router"]
    moe = d * p["router"] + (m["n_shared_experts"] + held) * glu
    dense = 3 * d * p["dense_d_ff"]
    n_moe = m["n_layers"] - p["first_dense_layers"]
    return (m["n_layers"] * mla_params(m, p) + p["first_dense_layers"] * dense
            + n_moe * moe + d * m["vocab_size"])


def attention_flops_per_token(m, keys: float, p=PUBLISHED) -> float:
    """Forward scores (q.k at head_dim) and weighted values (at v_head_dim)
    of one token over ``keys`` keys, every layer."""
    return m["n_layers"] * 2.0 * m["n_heads"] * keys * (
        m["head_dim"] + p["v_head_dim"])


def train_flops_per_token(m, seq: int) -> float:
    """Forward + backward (3x forward) per token of a causal row of
    ``seq + 1`` tokens, which attend (seq + 2) / 2 keys on average."""
    keys = (seq + 2) / 2.0
    return 3.0 * (2.0 * active_params(m) + attention_flops_per_token(m, keys))
