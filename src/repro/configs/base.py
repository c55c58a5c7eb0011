"""Config system: typed dataclasses for model / FFN / shape / mesh / run configs.

Design notes
------------
- Everything is a frozen dataclass; `replace(cfg, **kw)` / `cfg.override(**kw)` produce
  variants. Configs are pure data — no jax imports here, so importing a config never
  touches device state (required for the dry-run XLA_FLAGS dance).
- The registry maps ``--arch <id>`` strings to ModelConfig factories.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# FFN (the paper's subject)
# ---------------------------------------------------------------------------

FFN_KINDS = ("dense", "glu", "topk", "pkm", "sigma_moe", "switch", "sbase", "noisy_topk", "none")

# Kernel lowering of the planned execution layer (core/dispatch.py):
#   auto          defer to kernels.ops.default_impl() (pallas_fused on TPU,
#                 ragged elsewhere) — the production setting.
#   pallas_fused  fused streamed kernels (epilogues in-kernel); *_interpret
#   pallas        unfused planned kernels;                       variants run
#   ragged        lax.ragged_dot grouped matmul (MoE sort path)  the pallas
#   einsum        XLA take+einsum rung (weighted value sums)     kernels in
#   dense         bypass the planned layer entirely: full down-  interpret
#                 projection / dense 4-D value gather (oracle    mode (tests)
#                 reference for tests and ablations)
FFN_IMPLS = ("auto", "dense", "einsum", "ragged", "ref", "pallas",
             "pallas_interpret", "pallas_fused", "pallas_fused_interpret")


@dataclass(frozen=True)
class FFNConfig:
    """Configuration of one feedforward block (the paper's subject).

    kind:
      dense      -- y = W2 relu(W1 x)                     (paper Eq. 1-2)
      glu        -- y = W2 (act(W1 x) * W3 x)             (llama-family)
      topk       -- dense with top-K activation           (paper Sec. 3.1)
      pkm        -- product-key memory                    (paper Sec. 3.2)
      sigma_moe  -- the paper's sigma-MoE                 (paper Sec. 5)
      switch     -- Switch-Transformer routing            (paper Sec. 4)
      sbase      -- S-BASE (Sinkhorn)                     (paper Sec. 4)
      noisy_topk -- Shazeer 2017 sparsely-gated           (paper Sec. 4)
      none       -- no FFN at all (mamba2 blocks)
    """
    kind: str = "dense"
    d_ff: int = 0                      # total d_ff (= G * n_experts for MoE)
    activation: str = "relu"           # relu | gelu | silu | softmax (PKM ablation)
    # --- MoE family ---
    n_experts: int = 0                 # N_E
    expert_size: int = 0               # G (group size); d_ff = G * N_E
    k: int = 0                         # top-K experts
    selector_activation: str = "sigmoid"   # sigmoid | softmax | softmax_pre_topk
    renormalize: bool = False          # re-normalize scores after top-K
    expert_dropout: float = 0.0        # delta (Eq. 22)
    reg_gamma: float = 0.0             # entropy reg strength (Eq. 21)
    reg_kind: str = "entropy"          # entropy | switch | cv | seq_balance | none
    capacity_factor: float = 1.25      # mu, for capacity-based dispatch
    dispatch: str = "einsum"           # einsum | sort  (sort == CVMM path)
    impl: str = "auto"                 # kernel lowering, see FFN_IMPLS
    sigma_moe_init: bool = True        # paper's dense-equivalent init
    n_shared_experts: int = 0          # llama4-style always-on shared expert
    glu_experts: bool = False          # experts use GLU (for llama-family MoE)
    sinkhorn_iters: int = 8
    # --- a chip's share of the experts (expert parallelism) ---
    # The router scores ``router_experts`` experts (0: n_experts); this layer
    # holds the n_experts of them from ``expert_offset`` on and computes only
    # the (token, expert) rows routed to those. router_experts == n_experts
    # (every config that holds all its experts) is the plain layer.
    router_experts: int = 0
    expert_offset: int = 0
    routed_scale: float = 1.0          # gates times this (DeepSeek-V3 2.446)
    # DeepSeek-V3 auxiliary-loss-free balancing: a per-expert bias added to the
    # scores for choosing only, moved by bias_rate * sign(mean load - load)
    # after each step (runtime/steps.py). 0 = no bias.
    bias_rate: float = 0.0
    noise_std: float = 1.0             # noisy_topk
    # --- top-K activation (Sec 3.1) ---
    topk_k: int = 0
    # --- PKM (Sec 3.2) ---
    pkm_heads: int = 4
    pkm_knn: int = 32                  # K per head
    n_subkeys: int = 0                 # sqrt(d_ff); n_values = n_subkeys**2
    n_candidates: int = 0              # C: two-stage top-C per sub-key half
    #                                    (0 => C = pkm_knn, the minimum legal C)

    @property
    def n_router(self) -> int:
        """Experts the router scores (all experts of the layer)."""
        return self.router_experts or self.n_experts

    @property
    def holds_share(self) -> bool:
        """True when this layer holds only some of the experts it routes to."""
        return self.n_router != self.n_experts

    @property
    def n_values(self) -> int:
        """DERIVED from n_subkeys (the single source of truth): the PKM value
        table is always (n_subkeys**2, d_model), and init_pkm scales by this
        same quantity — a stale d_ff cannot silently mis-scale the paper's
        dense-equivalent value init (validated below)."""
        return self.n_subkeys * self.n_subkeys

    @property
    def pkm_candidates(self) -> int:
        """Effective two-stage candidate width C: top-C per sub-key half, the
        C*C candidate grid is re-scored to the final top-K. The true top-K of
        the full n_subkeys**2 grid is provably contained in the grid iff
        C >= K, so C defaults to pkm_knn when n_candidates is unset."""
        return self.n_candidates or self.pkm_knn

    def validate(self) -> None:
        assert self.kind in FFN_KINDS, self.kind
        assert self.impl in FFN_IMPLS, self.impl
        if self.kind in ("sigma_moe", "switch", "sbase", "noisy_topk"):
            assert self.n_experts > 0 and self.expert_size > 0 and self.k > 0
            assert 0 <= self.expert_offset and \
                self.expert_offset + self.n_experts <= self.n_router, (
                    f"held experts [{self.expert_offset}, "
                    f"{self.expert_offset + self.n_experts}) outside the "
                    f"router's {self.n_router}")
        if self.kind == "pkm":
            assert self.n_subkeys > 1
            # d_ff, when set for parameter accounting, must agree with the
            # derived value count — PKM's d_ff IS n_subkeys**2 (paper Sec 3.2).
            assert self.d_ff in (0, self.n_values), \
                f"pkm d_ff={self.d_ff} != n_subkeys**2={self.n_values}"
            # Two-stage candidate width: top-K over the C*C candidate grid
            # only provably equals the full top-K when each half contributes
            # at least K candidates (containment needs C >= K), and a C wider
            # than n_subkeys is impossible (each half only has n_subkeys
            # scores to take top-C from). Unset (0) means C = pkm_knn, the
            # minimum legal width, so only an explicit value needs checking.
            if self.n_candidates:
                assert self.n_candidates >= self.pkm_knn, (
                    f"pkm n_candidates={self.n_candidates} < pkm_knn="
                    f"{self.pkm_knn}: the two-stage C*C candidate grid can "
                    f"only contain the true top-K when C >= K (set "
                    f"n_candidates >= pkm_knn, or 0 for C=K)")
                assert self.n_candidates <= self.n_subkeys, (
                    f"pkm n_candidates={self.n_candidates} > n_subkeys="
                    f"{self.n_subkeys}: each half only has n_subkeys scores "
                    f"to take top-C from")
        if self.kind in ("dense", "glu", "topk"):
            assert self.d_ff > 0


def moe_ffn(n_experts: int, expert_size: int, k: int, **kw) -> FFNConfig:
    return FFNConfig(kind="sigma_moe", n_experts=n_experts, expert_size=expert_size,
                     k=k, d_ff=n_experts * expert_size, **kw)


# ---------------------------------------------------------------------------
# Attention / block / model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AttentionConfig:
    n_heads: int = 0
    n_kv_heads: int = 0                # GQA; == n_heads for MHA
    head_dim: int = 0
    rope_theta: float = 10000.0
    kind: str = "global"               # global | local (sliding window) | xl_rel | mla
    window: int = 0                    # sliding-window size for kind=local
    causal: bool = True
    qk_norm: bool = False
    softmax_scale: Optional[float] = None
    kv_chunk: int = 2048               # flash-attention KV chunk (pure-JAX path)
    # Multi-head latent attention (kind="mla", DeepSeek-V2 Sec. 2.1): keys and
    # values come up from a kv_lora_rank latent; head_dim is the query/key
    # width, of which the last rope_head_dim carry RoPE (the key part shared by
    # all heads); v_head_dim is the value width.
    kv_lora_rank: int = 0
    rope_head_dim: int = 0
    v_head_dim: int = 0

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def o_dim(self) -> int:
        """Width of the heads' concatenated outputs (input of W_o)."""
        return self.n_heads * (self.v_head_dim or self.head_dim)

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD block config."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256                   # SSD chunk length
    n_groups: int = 1                  # B/C groups (like GQA for SSM)

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class BlockSpecEntry:
    """One entry of a layer pattern: which mixer + which ffn."""
    mixer: str                          # "attn" | "ssm" | "shared_attn"
    ffn: str = "ffn"                    # "ffn" | "none" | "shared_ffn"
    attn_kind: str = ""                 # override attention kind ("local"/"global")


@dataclass(frozen=True)
class ModelConfig:
    name: str = ""
    family: str = "dense"              # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int = 0
    d_model: int = 0
    vocab_size: int = 0
    attention: AttentionConfig = field(default_factory=AttentionConfig)
    ffn: FFNConfig = field(default_factory=FFNConfig)
    ssm: Optional[SSMConfig] = None
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dropout: float = 0.0
    max_seq_len: int = 131072
    dtype: str = "bfloat16"            # compute dtype
    param_dtype: str = "float32"       # master dtype
    # Layer pattern. Empty => uniform [attn + ffn] * n_layers.
    # (pattern, repeat) pairs: pattern repeated; remainder handled by model builder.
    pattern: Tuple[BlockSpecEntry, ...] = ()
    # enc-dec (whisper)
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    n_audio_frames: int = 1500         # stub frontend output length
    # vlm: stub patch embeddings prepended — only affects input_specs
    n_vision_tokens: int = 0
    # XL-style segment memory (paper repro configs)
    xl_memory: int = 0
    # positional encoding
    pos_encoding: str = "rope"         # rope | xl_rel | learned | none
    # logit softcap (gemma-style), 0 = off
    logit_softcap: float = 0.0
    # sub-quadratic? (decides long_500k applicability)
    subquadratic: bool = False
    # leading layers whose FFN is a dense SiLU-GLU of width dense_d_ff in place
    # of cfg.ffn (DeepSeek's first_k_dense_replace)
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    # cast each scanned layer's weights to the compute dtype inside the layer
    # loop: otherwise XLA hoists one compute-dtype copy of the whole stack out
    # of the forward and backward loops, alive through the backward (1 GB at
    # the moonlight cut's 5 MoE layers)
    cast_weights_per_layer: bool = False

    # ---- derived ----
    @property
    def supports_decode(self) -> bool:
        return True                     # all our archs have a decoder

    def override(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def with_ffn(self, ffn: FFNConfig) -> "ModelConfig":
        return dataclasses.replace(self, ffn=ffn)

    def dense_ffn(self) -> FFNConfig:
        """The FFN of the leading dense layers."""
        return FFNConfig(kind="glu", d_ff=self.dense_d_ff,
                         activation=self.ffn.activation)

    def layer_pattern(self) -> List[BlockSpecEntry]:
        """Expanded per-layer pattern of length n_layers."""
        if not self.pattern:
            return [BlockSpecEntry(mixer="attn", ffn="ffn")] * self.n_layers
        out: List[BlockSpecEntry] = []
        i = 0
        while len(out) < self.n_layers:
            out.append(self.pattern[i % len(self.pattern)])
            i += 1
        return out[: self.n_layers]

    # ---- parameter counting (analytic; used for roofline MODEL_FLOPS) ----
    def ffn_params(self, ffn: Optional[FFNConfig] = None) -> Tuple[int, int]:
        """(total, active) parameter counts of one FFN block."""
        f = ffn or self.ffn
        d = self.d_model
        if f.kind == "none":
            return 0, 0
        if f.kind in ("dense", "topk"):
            p = 2 * d * f.d_ff
            active = 2 * d * (f.topk_k if (f.kind == "topk" and f.topk_k) else f.d_ff)
            # top-k still computes full up-projection (paper Sec 3.1)
            if f.kind == "topk":
                active = d * f.d_ff + d * (f.topk_k or f.d_ff)
            return p, active
        if f.kind == "glu":
            return 3 * d * f.d_ff, 3 * d * f.d_ff
        if f.kind == "pkm":
            p = 2 * f.n_subkeys * (d // 2) + f.n_values * d
            active = 2 * f.n_subkeys * (d // 2) + f.pkm_heads * f.pkm_knn * d
            return p, active
        # MoE family
        per_expert = (3 if f.glu_experts else 2) * d * f.expert_size
        p = f.n_experts * per_expert + f.n_router * d            # + router
        p += f.n_shared_experts * per_expert
        # a held share computes the k routed rows' held part on average
        k = f.k * f.n_experts / f.n_router
        active = int((k + f.n_shared_experts) * per_expert + f.n_router * d)
        return p, active

    def attn_params(self) -> int:
        a = self.attention
        d = self.d_model
        if a.kind == "mla":
            nope = a.head_dim - a.rope_head_dim
            return (d * a.q_dim + d * (a.kv_lora_rank + a.rope_head_dim)
                    + a.kv_lora_rank
                    + a.kv_lora_rank * a.n_heads * (nope + a.v_head_dim)
                    + a.o_dim * d)
        p = d * a.q_dim + 2 * d * a.kv_dim + a.q_dim * d
        if a.kind == "xl_rel":
            # Transformer-XL: relative-position projection W_r (+ small u/v biases).
            p += d * a.q_dim + 2 * a.q_dim
        return p

    def ssm_params(self) -> int:
        if self.ssm is None:
            return 0
        s = self.ssm
        d = self.d_model
        din = s.d_inner(d)
        nh = s.n_heads(d)
        # in_proj: x->(z, x, B, C, dt); conv; A, D, dt_bias; norm; out_proj
        conv_dim = din + 2 * s.n_groups * s.d_state
        in_proj = d * (2 * din + 2 * s.n_groups * s.d_state + nh)
        return in_proj + conv_dim * s.d_conv + 3 * nh + din + din * d

    def param_counts(self) -> Dict[str, int]:
        """Analytic totals: {'total': N, 'active': N_active, 'embedding': ...}."""
        d = self.d_model
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        total = emb + head
        body_total = 0
        body_active = 0
        shared_attn_counted = False
        shared_ffn_counted = False
        for li, entry in enumerate(self.layer_pattern()):
            if entry.mixer == "attn":
                body_total += self.attn_params()
                body_active += self.attn_params()
            elif entry.mixer == "shared_attn":
                if not shared_attn_counted:
                    body_total += self.attn_params()
                    shared_attn_counted = True
                body_active += self.attn_params()
            elif entry.mixer == "ssm":
                body_total += self.ssm_params()
                body_active += self.ssm_params()
            if entry.ffn == "ffn" and li < self.first_dense_layers:
                t, a = self.ffn_params(self.dense_ffn())
                body_total += t
                body_active += a
            elif entry.ffn == "ffn":
                t, a = self.ffn_params()
                body_total += t
                body_active += a
            elif entry.ffn == "shared_ffn":
                t, a = self.ffn_params()
                if not shared_ffn_counted:
                    body_total += t
                    shared_ffn_counted = True
                body_active += a
        if self.is_encoder_decoder:
            # encoder layers: self-attn + ffn (non-causal), plus decoder cross-attn
            enc = self.n_encoder_layers * (self.attn_params() + self.ffn_params()[0])
            cross = self.n_layers * self.attn_params()
            body_total += enc + cross
            body_active += enc + cross
        total += body_total
        # "active" params per token: unembedding matmul + body active path.
        # (Embedding lookup is a gather, conventionally excluded from 6ND.)
        return {
            "total": total,
            "active": head + body_active,
            "embedding": emb + head,
            "body": body_total,
            "body_active": body_active,
        }


# ---------------------------------------------------------------------------
# Input shapes (assigned set)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                          # train | prefill | decode

SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Mesh / run configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def n_devices(self) -> int:
        return math.prod(self.shape)

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axes

    def axis_size(self, name: str) -> int:
        return self.shape[self.axes.index(name)] if name in self.axes else 1


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 2.5e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 0.25
    schedule: str = "cosine"           # cosine | wsd | constant
    warmup_steps: int = 0
    total_steps: int = 100_000
    final_lr_ratio: float = 0.0
    grad_accum: int = 1
    grad_compression: str = "none"     # none | bf16 | int8  (error-feedback)


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig = field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    seq_len: int = 256
    global_batch: int = 64
    steps: int = 100
    seed: int = 0
    log_every: int = 10
    checkpoint_every: int = 200
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    remat: str = "full"                # full | dots | none
    sequence_parallel: bool = False    # SP sharding constraint on residual stream
    chunked_ce_chunks: int = 1         # >1 enables chunked cross-entropy
    async_checkpoint: bool = True
    data: str = "synthetic"            # synthetic | <path to text file>
