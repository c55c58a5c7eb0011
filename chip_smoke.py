#!/usr/bin/env python3
"""Bring the main paths up on a TPU and check what they compute.

    python3 chip_smoke.py              # xl_rel, train, serve on one chip
    python3 chip_smoke.py --chips 4    # mesh training on four chips only

xl_rel the shifted BD kernel of xl_rel attention against the XLA path it
       replaces, forward and VJP, at the training shapes and at one query,
       no memory, and every residue of the query count mod 8.
train  wt103-262m-moe as registered (paper Tab. 8/9: 18 layers, d_model 1024,
       32 experts of 128, k=4, xl_rel attention with 512 memory), batch 8 x
       seq 512, 6 steps through ``repro.launch.train`` on a 1x1 mesh, with
       full rematerialization (without it the step needs 16.8 GB of HBM).
       Checks: finite losses, last loss below the first, and step 0's loss
       and grad norm against the same step with ``FFNConfig.impl="ragged"``.
serve  granite-moe-3b-a800m at its published widths with bf16 parameters and
       the dropless ``sort`` dispatch: 8 requests of 64-256 prompt tokens and
       32 new tokens through the continuous-batching ``Engine``. Checks: every
       request gets its tokens, the decode provider served, and the paged
       path's logits after prefill and after the first decode steps match the
       contiguous forward with ``impl="ragged"``.
--chips 4  the train phase on ``--mesh 4x1`` and ``--mesh 2x2`` against the
       same job on one chip: losses agree, and every parameter sits on four
       devices with the sharding the rules give it. The Mosaic kernels run
       per device inside ``shard_map``s (the sort dispatch, xl_rel's BD term).

Weights are random, made from ``--seed``. Everything runs in this one process.
A failed check raises, so the script exits non-zero; where JAX finds no TPU it
exits non-zero before any phase. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

TRAIN_ARCH = "wt103-262m-moe"
SERVE_ARCH = "granite-moe-3b-a800m"

# Tolerances, fixed before any chip run. The models compute in bfloat16
# (unit roundoff 2**-8) and the references differ only in how the expert
# GEMMs round. Scalars averaged over thousands of tokens (loss, grad norm)
# may differ by a few roundoffs; serving logits pass through 32 layers of
# bf16 activations (about sqrt(32) * 2**-8 = 2.2e-2 relative) and routing
# near-ties, so they get four times the scalar bound.
SCALAR_RTOL = 2.0 ** -6
LOGITS_RTOL = 2.0 ** -4


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SmokeError(what)


def close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * abs(b)


def sort_rung(cfg, dtype) -> str:
    """The rung the sort dispatch takes for ``cfg``'s expert MLP."""
    from repro.core.dispatch import resolve_impl
    from repro.kernels import ops
    f = cfg.ffn
    return ops.plan_sort_kernels(resolve_impl(f), cfg.d_model, f.expert_size,
                                 f.activation, dtype, glu=f.glu_experts).rung


# ---------------------------------------------------------------------------
# xl_rel
# ---------------------------------------------------------------------------

# (batch, heads, head_dim, queries, keys): the training shapes of wt103-262m
# and enwik8-41m (rows of 512 + 1 over 512 of memory) and of wt103-47m
# (256 + 1 over 256), then one query row, no memory, and eight queries
# 300-307, one of each residue mod 8. Mosaic's strided roll went wrong at
# some residues and rotations that interpret mode and the training shapes
# never showed (kernels/xl_rel.py, SUBLANE).
XL_REL_SHAPES = ((16, 16, 64, 513, 1025), (16, 10, 41, 257, 513),
                 (2, 3, 41, 37, 301), (2, 2, 41, 300, 300),
                 (2, 2, 64, 1, 513)) + tuple(
                     (1, 2, 64, 300 + k, 511 + k) for k in range(8))
# bfloat16 inputs, float32 accumulation on both paths. A forward value may
# differ by one bfloat16 rounding of the tensor's largest; the VJP's two
# outputs by 2**-8 in norm. A shifted row is off by the whole value and a
# wrongly rolled cotangent by percents (the faults seen: 3% to 16x).
XL_REL_FWD_RTOL = 2.0 ** -8
XL_REL_VJP_RTOL = 2.0 ** -8


def xl_rel_phase(shapes=XL_REL_SHAPES, *, seed: int = 0,
                 interpret: bool = False):
    """The shifted BD kernel (``kernels/xl_rel.py``) against the XLA path it
    replaces on TPU, einsum + ``_rel_shift``: the forward on the causal-valid
    region, and the VJP of a cotangent on that region (d(q + v_bias), dr).
    Returns the largest (forward, VJP) relative errors."""
    from repro.kernels.xl_rel import xl_rel_bd
    from repro.models.attention import _rel_shift

    def xla(qv, r):
        return _rel_shift(jnp.einsum("bqhd,khd->bhqk", qv, r))

    def both(f, qv, r, g):
        out, vjp = jax.vjp(f, qv, r)
        return out, vjp(g)

    kernel = jax.jit(functools.partial(
        both, functools.partial(xl_rel_bd, interpret=interpret)))
    reference = jax.jit(functools.partial(both, xla))
    worst = [0.0, 0.0]
    for b, h, d, sq, sk in shapes:
        keys = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(seed), sq * 4096 + sk), 3)
        qv = jax.random.normal(keys[0], (b, sq, h, d)).astype(jnp.bfloat16)
        r = jax.random.normal(keys[1], (sk, h, d)).astype(jnp.bfloat16)
        valid = jnp.arange(sk)[None, :] <= (sk - sq) + jnp.arange(sq)[:, None]
        g = (jax.random.normal(keys[2], (b, h, sq, sk))
             * valid).astype(jnp.bfloat16)
        (got, got_ct), (want, want_ct) = kernel(qv, r, g), reference(qv, r, g)
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        fwd = float(jnp.max(jnp.where(valid, jnp.abs(got - want), 0))
                    / jnp.max(jnp.abs(want)))
        vjp = max(float(jnp.linalg.norm((a.astype(jnp.float32)
                                         - e.astype(jnp.float32)).ravel())
                        / jnp.linalg.norm(e.astype(jnp.float32).ravel()))
                  for a, e in zip(got_ct, want_ct))
        check(bool(jnp.all(jnp.isfinite(got))) and fwd <= XL_REL_FWD_RTOL
              and vjp <= XL_REL_VJP_RTOL,
              f"xl_rel: {(b, h, d, sq, sk)} finite, forward {fwd:.2e} <= "
              f"{XL_REL_FWD_RTOL:.2e}, VJP {vjp:.2e} <= {XL_REL_VJP_RTOL:.2e}"
              f" of the XLA path")
        worst = [max(worst[0], fwd), max(worst[1], vjp)]
    return tuple(worst)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_run(arch: str, *, steps: int, batch: int, seq: int, seed: int = 0,
              mesh: str = "1x1", impl: str = "auto", reduced: bool = False):
    """One ``repro.launch.train`` job; its checkpoints go to a temporary
    directory that is removed afterwards."""
    from repro.launch import train
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        return train.run(
            ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
             "--seq", str(seq), "--seed", str(seed), "--mesh", mesh,
             "--impl", impl, "--remat", "full", "--ckpt-every", "0",
             "--log-every", "1", "--ckpt-dir", ckpt]
            + (["--reduced"] if reduced else []))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def train_phase(arch: str = TRAIN_ARCH, *, steps: int = 6, batch: int = 8,
                seq: int = 512, seed: int = 0, impl: str = "auto",
                reduced: bool = False):
    """Train ``steps`` steps, then step 0 again with ragged_dot experts.
    Returns (run, reference run), both without their device state."""
    kw = dict(batch=batch, seq=seq, seed=seed, reduced=reduced)
    run = train_run(arch, steps=steps, impl=impl, **kw)._replace(state=None)
    losses = [h["loss"] for h in run.history]
    check(len(losses) == steps and all(np.isfinite(losses)),
          f"train: {steps} finite losses {losses}")
    check(losses[-1] < losses[0],
          f"train: last loss {losses[-1]} < first {losses[0]}")
    ref = train_run(arch, steps=1, impl="ragged", **kw)._replace(state=None)
    got, want = run.history[0], ref.history[0]
    for key in ("loss", "grad_norm"):
        check(close(got[key], want[key], SCALAR_RTOL),
              f"train: step 0 {key} {got[key]} vs ragged {want[key]} "
              f"(rtol {SCALAR_RTOL})")
    return run, ref


def four_chip_phase(arch: str = TRAIN_ARCH, *, steps: int = 4,
                    batch: int = 8, seq: int = 512, seed: int = 0,
                    meshes=("4x1", "2x2"), impl: str = "auto",
                    reduced: bool = False):
    """The train job on each of ``meshes`` against the same job on one chip:
    per-step losses agree, and every parameter is spread over all devices of
    the mesh with the sharding TRAIN_RULES gives it."""
    from repro.sharding import TRAIN_RULES, tree_shardings
    kw = dict(steps=steps, batch=batch, seq=seq, seed=seed, impl=impl,
              reduced=reduced)
    base = [h["loss"] for h in train_run(arch, mesh="1x1", **kw).history]
    for mesh in meshes:
        run = train_run(arch, mesh=mesh, **kw)
        n_dev = int(np.prod([int(x) for x in mesh.split("x")]))
        params = run.state["params"]
        leaves = jax.tree_util.tree_leaves_with_path(params)
        want = tree_shardings(run.state, leaves[0][1].sharding.mesh,
                              TRAIN_RULES)["params"]
        bad = [jax.tree_util.keystr(path) for (path, leaf), sh in
               zip(leaves, jax.tree_util.tree_leaves(want))
               if len({s.device for s in leaf.addressable_shards}) != n_dev
               or not leaf.sharding.is_equivalent_to(sh, leaf.ndim)]
        check(not bad, f"mesh {mesh}: all {len(leaves)} parameters on "
                       f"{n_dev} devices as TRAIN_RULES say (off: {bad[:4]})")
        losses = [h["loss"] for h in run.history]
        check(all(close(a, b, SCALAR_RTOL) for a, b in zip(losses, base)),
              f"mesh {mesh}: losses {losses} vs one chip {base} "
              f"(rtol {SCALAR_RTOL})")
        del run, params, leaves


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_config(arch: str = SERVE_ARCH, *, reduced: bool = False,
                 impl: str = "auto"):
    """The arch at its published widths with bf16 parameters (granite's 3.3B
    in f32 would not leave room on a 16 GB chip) and the dropless sort
    dispatch that the decode provider serves."""
    from repro.configs import get_config, reduced as reduced_cfg
    cfg = reduced_cfg(arch) if reduced else get_config(arch)
    return cfg.override(param_dtype="bfloat16").with_ffn(
        dataclasses.replace(cfg.ffn, dispatch="sort", impl=impl))


def paged_logits(lm, params, prompts, tokens, n_decode: int, page_size: int,
                 chunk: int):
    """Logits of the paged path, teacher-forced with the engine's tokens:
    after each prompt's chunked prefill, then after each of ``n_decode``
    batched decode steps. Returns (n_requests, n_decode + 1, V)."""
    from repro.serving import PagedKVCache
    n = len(prompts)
    n_blocks = -(-(max(map(len, prompts)) + n_decode) // page_size)
    kv = PagedKVCache(1 + n * n_blocks, page_size)
    cache = lm.init_paged_cache(1 + n * n_blocks, page_size)
    prefill = jax.jit(lm.prefill_paged, donate_argnums=(2,))
    decode = jax.jit(lm.decode_step_paged, donate_argnums=(1,))
    tables, first = [], []
    for i, prompt in enumerate(prompts):
        kv.alloc(i, len(prompt) + n_decode)
        tables.append(kv.block_table(i, n_blocks))
        for start in range(0, len(prompt), chunk):
            part = prompt[start:start + chunk]
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :len(part)] = part
            lg, cache = prefill(params, jnp.asarray(buf), cache,
                                jnp.asarray(tables[i][None]),
                                jnp.int32(start), jnp.int32(len(part)))
        first.append(np.asarray(lg[0], np.float32))
    out = [np.stack(first)]
    tables = jnp.asarray(np.stack(tables))
    lens = np.array([len(p) for p in prompts], np.int32)
    for j in range(n_decode):
        tok = jnp.asarray([t[j] for t in tokens], jnp.int32)
        lg, cache = decode(params, cache, tok, jnp.asarray(lens + j), tables)
        out.append(np.asarray(lg, np.float32))
    return np.stack(out, axis=1)


def reference_logits(lm, params, prompts, tokens, n_decode: int):
    """The contiguous forward's logits at the positions ``paged_logits``
    reads: each prompt's last token and the first ``n_decode`` generated."""
    seqs = [list(p) + list(t[:n_decode]) for p, t in zip(prompts, tokens)]
    buf = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        buf[i, :len(s)] = s                  # causal: the zero tail is unseen
    pos = np.array([[len(p) - 1 + j for j in range(n_decode + 1)]
                    for p in prompts], np.int32)
    fn = jax.jit(lambda p, t, q: jnp.take_along_axis(
        lm.logits(p, t), q[..., None], axis=1))
    return np.asarray(fn(params, jnp.asarray(buf), jnp.asarray(pos)),
                      np.float32)


def serve_phase(cfg, *, seed: int = 0, n_requests: int = 8,
                prompt_len=(64, 256), max_new: int = 32, max_batch: int = 8,
                page_size: int = 16, prefill_chunk: int = 64,
                n_decode_checked: int = 4):
    """Serve ``n_requests`` seeded prompts through the Engine and check them.
    Prefill chunks take the sort path's rung; decode steps of up to
    ``max_batch`` lanes go through the cached-plan decode provider."""
    from repro.common import round_up
    from repro.kernels import ops
    from repro.models.lm import LM
    from repro.serving import Engine, Request

    lm = LM(cfg)
    params = jax.jit(lm.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lo, hi = prompt_len
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(lo, hi + 1))).tolist()
               for _ in range(n_requests)]
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    with Engine(lm, params, max_batch=max_batch,
                max_len=round_up(hi + max_new, page_size),
                page_size=page_size, prefill_chunk=prefill_chunk,
                decode_plan_max_tokens=max_batch) as eng:
        t0 = time.time()
        outs = eng.run(reqs)
        wall = time.time() - t0
        print(f"[serve] dispatch={cfg.ffn.dispatch} engine stats {eng.stats} "
              f"wall {wall:.1f}s (includes compile)", flush=True)
        check(all(len(outs[i]) == max_new for i in range(n_requests)),
              f"serve: all {n_requests} requests returned {max_new} tokens")
        tokens = [outs[i] for i in range(n_requests)]
        served = eng.plan_cache.counters()
        got = paged_logits(lm, params, prompts, tokens, n_decode_checked,
                           page_size, prefill_chunk)
        counters = eng.plan_cache.counters()
    # The provider runs at trace time, once per decode shape (jit traces a
    # shape once, so ``hits`` stays 0 unless a shape is traced again): the
    # engine's bursts must have built plans, and the checked decode steps
    # must have asked the provider too. A real plan for that shape means the
    # kernels ran instead of the provider declining.
    f = cfg.ffn
    plan = ops.make_decode_plan(n_requests, f.k, f.n_experts, cfg.d_model,
                                f.expert_size, lm.dtype)
    asked = (counters["rebuilds"] + counters["hits"]
             - served["rebuilds"] - served["hits"])
    check(plan is not None and served["rebuilds"] > 0 and asked > 0,
          f"serve: decode provider served the engine (plan cache {served}) "
          f"and the {n_requests}-lane checked decode (plan cache {counters})")
    ref_lm = LM(cfg.with_ffn(dataclasses.replace(cfg.ffn, impl="ragged")))
    want = reference_logits(ref_lm, params, prompts, tokens,
                            n_decode_checked)
    # Only the real vocabulary: the padded tail holds -1e30 in both, whose
    # squares overflow and would make every error 0.
    v = cfg.vocab_size
    err = (np.linalg.norm(got[..., :v] - want[..., :v], axis=-1)
           / np.linalg.norm(want[..., :v], axis=-1))
    check(bool(np.all(np.isfinite(err)) and np.all(err <= LOGITS_RTOL)),
          f"serve: paged logits vs contiguous ragged forward, max relative "
          f"L2 error {float(err.max()):.3e} (rtol {LOGITS_RTOL}) over "
          f"{err.size} (request, step) pairs")
    engine_tok = np.array([t[:n_decode_checked + 1] for t in tokens])
    agree = float(np.mean(want.argmax(-1) == engine_tok))
    print(f"[serve] engine tokens equal to the reference argmax: "
          f"{agree:.3f} of {engine_tok.size}", flush=True)
    return dict(outs=outs, counters=counters, err=err, agree=agree)


# ---------------------------------------------------------------------------

def _memory_line(dev) -> str:
    stats = dev.memory_stats() or {}
    return f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.kernels import autotune
    print(f"[device] {dev.platform} {dev.device_kind!r} x{len(devices)}; "
          f"compile cache {use_compile_cache()}", flush=True)
    autotune.enable(False)          # tiles from the heuristic, no cache file
    check(len(devices) == args.chips,
          f"{args.chips} chip(s) requested, JAX sees {len(devices)}")

    if args.chips == 4:
        four_chip_phase(seed=args.seed)
        print(f"[four-chip] {_memory_line(dev)}", flush=True)
    else:
        xl_rel_phase(seed=args.seed)
        from repro.configs import get_config
        rung = sort_rung(get_config(TRAIN_ARCH), jnp.bfloat16)
        print(f"[train] {TRAIN_ARCH} sort rung {rung}", flush=True)
        check(rung == "pallas_fused", f"train: rung {rung} is pallas_fused")
        run, ref = train_phase(seed=args.seed)
        n_kernels = run.compiled.as_text().count("tpu_custom_call")
        check(n_kernels > 0,
              f"train: compiled step holds {n_kernels} tpu_custom_call")
        print(f"[train] compile {run.compile_s:.1f}s, ragged reference "
              f"compile {ref.compile_s:.1f}s, {_memory_line(dev)}",
              flush=True)
        del run, ref

        cfg = serve_config()
        rung = sort_rung(cfg, jnp.bfloat16)
        print(f"[serve] {SERVE_ARCH} prefill sort rung {rung}; decode "
              f"through the cached-plan provider", flush=True)
        check(rung == "pallas_fused", f"serve: prefill rung {rung} is "
                                      f"pallas_fused")
        serve_phase(cfg, seed=args.seed)
        print(f"[serve] {_memory_line(dev)}", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
