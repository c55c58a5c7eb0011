"""Unified language model: decoder-only (dense / MoE / SSM / hybrid / VLM) and
encoder-decoder (whisper) in one functional class.

Public step surface (consumed by runtime/ and launch/):
    init(key) -> params
    loss(params, batch, rng, train) -> (loss, metrics)          [train_4k]
    prefill(params, batch) -> (last_logits, cache)               [prefill_32k]
    decode_step(params, cache, token, pos) -> (logits, cache)    [decode_32k/long_500k]
    init_cache(batch_size, max_len) -> cache
    input_specs(shape) / state_specs(shape) -> ShapeDtypeStructs for the dry-run
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import BlockSpecEntry, ModelConfig, ShapeConfig
from ..sharding.logical import SP_RULES, with_logical_constraint
from .layers import apply_norm, dropout, init_embedding, init_norm
from .stack import (apply_stack, cross_kv_cache, init_paged_stack_cache,
                    init_stack, init_stack_cache, plan_segments)


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return logits
    return cap * jnp.tanh(logits / cap)


class LM:
    def __init__(self, cfg: ModelConfig, *, remat: str = "none",
                 sequence_parallel: bool = False, ce_chunks: int = 0,
                 ep_degree: int = 0):
        self.cfg = cfg
        self.remat = remat
        self.sp = sequence_parallel
        self.ep_degree = ep_degree
        # auto chunked-CE: bound the (tokens x vocab) logits buffer
        self.ce_chunks = ce_chunks
        self.dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        # vocab padded to a TP-friendly multiple (MaxText-style); padded logit
        # columns are masked to -inf everywhere they can leak out.
        from ..common import round_up
        self.vocab_padded = round_up(cfg.vocab_size, 512)

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = jax.random.split(key, 6)
        p: Dict[str, Any] = {
            "emb": init_embedding(keys[0], self.vocab_padded, cfg.d_model,
                                  self.param_dtype),
            "final_norm": init_norm(cfg, cfg.d_model, self.param_dtype),
            "stack": init_stack(keys[1], cfg, self.param_dtype,
                                ep_degree=self.ep_degree,
                                cross=cfg.is_encoder_decoder),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = init_embedding(keys[2], cfg.d_model, self.vocab_padded,
                                          self.param_dtype) * (cfg.d_model ** -0.5)
        if cfg.pos_encoding == "learned":
            p["pos_emb"] = 0.01 * jax.random.normal(
                keys[3], (cfg.max_seq_len, cfg.d_model), self.param_dtype)
        if cfg.is_encoder_decoder:
            enc_cfg = self._encoder_cfg()
            p["enc_stack"] = init_stack(keys[4], enc_cfg, self.param_dtype,
                                        n_layers=cfg.n_encoder_layers)
            p["enc_norm"] = init_norm(cfg, cfg.d_model, self.param_dtype)
            p["enc_pos"] = 0.01 * jax.random.normal(
                keys[5], (cfg.n_audio_frames, cfg.d_model), self.param_dtype)
        return p

    def _encoder_cfg(self) -> ModelConfig:
        return self.cfg.override(
            pattern=(BlockSpecEntry(mixer="attn", ffn="ffn",
                                    attn_kind="noncausal"),),
            pos_encoding="learned")

    # -------------------------------------------------------------- embedding
    @jax.named_scope("embed")
    def _embed(self, params, tokens, *, prefix_embeds=None, pos_offset=0):
        cfg = self.cfg
        x = params["emb"].astype(self.dtype)[tokens]
        if cfg.pos_encoding == "learned":
            s = tokens.shape[1]
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_emb"].astype(self.dtype), pos_offset, s, axis=0)
            x = x + pe[None]
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(self.dtype), x], axis=1)
        return x

    def _unembed(self, params, h):
        cfg = self.cfg
        w = (params["emb"].T if cfg.tie_embeddings else params["unembed"])
        logits = jnp.einsum("...d,dv->...v", h, w.astype(h.dtype))
        logits = _softcap(logits.astype(jnp.float32), cfg.logit_softcap)
        if self.vocab_padded != cfg.vocab_size:
            valid = jnp.arange(self.vocab_padded) < cfg.vocab_size
            logits = jnp.where(valid, logits, -1e30)
        return logits

    def _encode(self, params, frames, *, rng=None, train=False):
        cfg = self.cfg
        x = frames.astype(self.dtype) + params["enc_pos"].astype(self.dtype)[None]
        x, aux, _, _ = apply_stack(params["enc_stack"], x, self._encoder_cfg(),
                                   rng=rng, train=train, remat=self.remat,
                                   sp=self.sp, n_layers=cfg.n_encoder_layers)
        return apply_norm(params["enc_norm"], x, cfg), aux

    # ------------------------------------------------------------------ train
    def forward(self, params, tokens, *, prefix_embeds=None, frames=None,
                rng=None, train=False, mems=None):
        """Full-sequence forward -> (hidden, aux, new_mems)."""
        cfg = self.cfg
        r_emb = r_stack = None
        if rng is not None:
            r_emb, r_stack = jax.random.split(rng)
        x = self._embed(params, tokens, prefix_embeds=prefix_embeds)
        x = dropout(r_emb, x, cfg.dropout, train)
        x = (with_logical_constraint(x, ("batch", "seq", None), SP_RULES)
             if self.sp else with_logical_constraint(x, ("batch", None, None)))
        enc_out = None
        aux_e = {}
        if cfg.is_encoder_decoder:
            enc_out, aux_e = self._encode(params, frames, rng=rng, train=train)
        positions = jnp.arange(x.shape[1])
        x, aux, _, new_mems = apply_stack(
            params["stack"], x, cfg, rng=r_stack, train=train,
            positions=positions, mems=mems, enc_out=enc_out,
            remat=self.remat, sp=self.sp)
        if aux_e:
            aux = {k: aux[k] + aux_e.get(k, 0.0) for k in aux}
        x = apply_norm(params["final_norm"], x, cfg)
        return x, aux, new_mems

    def loss(self, params, batch: Dict, rng=None, train: bool = True,
             mems=None) -> Tuple[jax.Array, Dict]:
        """Next-token CE (+ MoE regularizers). batch: tokens (B,S) [, frames/patches].

        Vision prefix tokens are unsupervised; labels are tokens shifted by one.
        """
        from ..runtime.loss import chunked_cross_entropy
        cfg = self.cfg
        tokens = batch["tokens"]
        prefix = batch.get("patches")
        h, aux, new_mems = self.forward(
            params, tokens, prefix_embeds=prefix, frames=batch.get("frames"),
            rng=rng, train=train, mems=mems)
        n_prefix = prefix.shape[1] if prefix is not None else 0
        with jax.named_scope("loss"):
            h_text = h[:, n_prefix:, :]
            w = (params["emb"].T if cfg.tie_embeddings else params["unembed"])
            ce, n_tok = chunked_cross_entropy(
                h_text[:, :-1], w.astype(h_text.dtype), tokens[:, 1:],
                chunks=self.ce_chunks, softcap=cfg.logit_softcap,
                n_valid_vocab=(cfg.vocab_size
                               if self.vocab_padded != cfg.vocab_size else 0))
        loss = ce + aux["moe_reg"]
        metrics = {"ce": ce, "moe_reg": aux["moe_reg"],
                   "moe_dropped": aux["moe_dropped"], "tokens": n_tok}
        # selection-bias layers: their loads (for the step's bias update)
        # and the rows their held experts computed
        metrics.update({k: aux[k] for k in ("loads", "moe_rows_held")
                        if k in aux})
        return loss, (metrics if mems is None else (metrics, new_mems))

    def update_selection_bias(self, new_params: Dict, params: Dict,
                              loads: Dict) -> Dict:
        """``new_params`` with each MoE layer's selection bias moved from its
        value in ``params`` by the layer's expert loads (``loads`` as
        ``forward`` reports them; core/moe.update_selection_bias)."""
        from ..core.moe import update_selection_bias
        segs = []
        for new, old, seg_loads in zip(new_params["stack"]["segments"],
                                       params["stack"]["segments"],
                                       loads["segments"]):
            new = dict(new)
            for name, load in seg_loads.items():
                ffn = dict(new[name]["ffn"])
                ffn["router_bias"] = {"bias": update_selection_bias(
                    old[name]["ffn"]["router_bias"]["bias"], load,
                    self.cfg.ffn.bias_rate)}
                new[name] = dict(new[name], ffn=ffn)
            segs.append(new)
        stack = dict(new_params["stack"], segments=segs)
        return dict(new_params, stack=stack)

    def logits(self, params, tokens: jax.Array) -> jax.Array:
        """(B, S, V) next-token logits of the contiguous full-sequence
        forward (eval mode, no cache): the reference that the paged serving
        path is checked against."""
        h, _, _ = self.forward(params, tokens)
        return self._unembed(params, h)

    # ---------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_len: int) -> Dict:
        return init_stack_cache(self.cfg, batch, max_len, self.dtype)

    def prefill(self, params, batch: Dict, cache: Dict) -> Tuple[jax.Array, Dict]:
        """Run the prompt through the stack, filling `cache`; returns last logits."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, prefix_embeds=batch.get("patches"))
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out, _ = self._encode(params, batch["frames"])
            cache = self._attach_cross_caches(params, cache, enc_out)
        positions = jnp.arange(x.shape[1])
        x, _, new_cache, _ = apply_stack(
            params["stack"], x, cfg, positions=positions, cache=cache,
            cache_index=jnp.int32(0), enc_out=None, remat=self.remat, sp=self.sp)
        x = apply_norm(params["final_norm"], x[:, -1:, :], cfg)
        return self._unembed(params, x)[:, 0], new_cache

    def _attach_cross_caches(self, params, cache, enc_out):
        """Precompute per-decoder-layer cross K/V (whisper)."""
        segs = plan_segments(self.cfg)
        new_cache = {"segments": []}
        for si, seg in enumerate(segs):
            seg_params = params["stack"]["segments"][si]
            seg_cache = dict(cache["segments"][si])
            for ei, entry in enumerate(seg.entries):
                stacked = seg_params[f"e{ei}"]
                if "cross" not in stacked:
                    continue
                cross = jax.vmap(
                    lambda cp: cross_kv_cache(cp, enc_out, self.cfg))(stacked["cross"])
                ec = dict(seg_cache[f"e{ei}"])
                ec["cross"] = cross
                seg_cache[f"e{ei}"] = ec
            new_cache["segments"].append(seg_cache)
        return new_cache

    # --------------------------------------------------------- paged serving
    def _check_paged_support(self) -> None:
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "paged serving: encoder-decoder models unsupported")
        if cfg.pos_encoding not in ("rope", "none"):
            raise NotImplementedError(
                f"paged serving: pos_encoding={cfg.pos_encoding!r} unsupported"
                " (per-request offsets need position-free embeddings)")
        if cfg.n_vision_tokens:
            raise NotImplementedError("paged serving: vision prefix unsupported")
        if cfg.attention.kind == "mla":
            raise NotImplementedError(
                "paged serving: mla attention unsupported (a paged cache of "
                "the compressed latent and the shared RoPE key does not "
                "exist yet)")

    def init_paged_cache(self, n_pages: int, page_size: int) -> Dict:
        """Paged KV pool shared by all requests; page 0 is the reserved
        null/scratch page (never handed out by the allocator). The pool shape
        is batch-independent: per-request placement lives in block tables."""
        self._check_paged_support()
        return init_paged_stack_cache(self.cfg, n_pages, page_size, self.dtype)

    def prefill_paged(self, params, tokens: jax.Array, cache: Dict,
                      block_table: jax.Array, start, length
                      ) -> Tuple[jax.Array, Dict]:
        """Prefill ONE request's chunk into the paged pool.

        tokens (1, S) fixed-size padded chunk, block_table (1, n_blocks),
        start = absolute offset of this chunk in the request, length = number
        of valid tokens in the chunk (<= S; the padded tail is dropped on the
        reserved OOB page). Returns (logits at the last valid token (1, V),
        new_cache).
        """
        cfg = self.cfg
        x = self._embed(params, tokens)
        positions = start + jnp.arange(tokens.shape[1])
        seq_lens = jnp.asarray(length, jnp.int32).reshape(1)
        x, _, new_cache, _ = apply_stack(
            params["stack"], x, cfg, positions=positions, cache=cache,
            cache_index=start, block_table=block_table, seq_lens=seq_lens,
            sp=False)
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(jnp.asarray(length, jnp.int32) - 1, 0), 1, axis=1)
        last = apply_norm(params["final_norm"], last, cfg)
        return self._unembed(params, last)[:, 0], new_cache

    def decode_step_paged(self, params, cache: Dict, token: jax.Array,
                          positions: jax.Array, block_tables: jax.Array
                          ) -> Tuple[jax.Array, Dict]:
        """One batched paged decode step. token (B,), positions (B,) absolute
        per-request positions, block_tables (B, n_blocks)."""
        cfg = self.cfg
        x = self._embed(params, token[:, None])
        x, _, new_cache, _ = apply_stack(
            params["stack"], x, cfg, positions=positions[:, None], cache=cache,
            cache_index=positions, block_table=block_tables, sp=False)
        x = apply_norm(params["final_norm"], x, cfg)
        return self._unembed(params, x)[:, 0], new_cache

    def decode_step(self, params, cache: Dict, token: jax.Array,
                    pos) -> Tuple[jax.Array, Dict]:
        """One batched decode step. token (B,), pos scalar int32."""
        cfg = self.cfg
        x = self._embed(params, token[:, None], pos_offset=pos)
        positions = jnp.full((token.shape[0], 1), pos, jnp.int32)
        x, _, new_cache, _ = apply_stack(
            params["stack"], x, cfg, positions=positions, cache=cache,
            cache_index=pos, sp=False)
        x = apply_norm(params["final_norm"], x, cfg)
        return self._unembed(params, x)[:, 0], new_cache

    # ----------------------------------------------------------------- specs
    def input_specs(self, shape: ShapeConfig) -> Dict:
        """ShapeDtypeStruct stand-ins for every model input (dry-run; no allocation)."""
        cfg = self.cfg
        b = shape.global_batch
        s = shape.seq_len
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs: Dict[str, Any] = {}
        if shape.mode in ("train", "prefill"):
            n_vis = cfg.n_vision_tokens
            specs["tokens"] = jax.ShapeDtypeStruct((b, s - n_vis), jnp.int32)
            if n_vis:
                specs["patches"] = jax.ShapeDtypeStruct((b, n_vis, cfg.d_model),
                                                        self.dtype)
            if cfg.is_encoder_decoder:
                specs["frames"] = jax.ShapeDtypeStruct(
                    (b, cfg.n_audio_frames, cfg.d_model), self.dtype)
        else:  # decode
            specs["token"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        return specs
