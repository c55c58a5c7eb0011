"""Fault-tolerant training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch wt103-47m-moe --steps 200 \
        --batch 8 --seq 128 --mesh 1x1 [--resume] [--data synthetic|/path/corpus]

Fault-tolerance posture (exercised by tests/test_fault_tolerance.py):
  * every state leaf (params, optimizer, error-feedback, XL mems, data-iterator
    state, RNG) lives in ONE checkpointed pytree -> restart is bit-exact;
  * checkpoints are atomic + async (CheckpointManager); SIGTERM/preemption between
    commits loses at most `checkpoint_every` steps;
  * the step loop tolerates transient compute errors by restoring the last
    checkpoint (restart-in-place) before re-raising persistent ones;
  * straggler monitor flags slow steps for the orchestrator.

Tracing: every step runs inside ``jax.profiler.StepTraceAnnotation("train",
step_num=step)`` with host spans ``train.batch`` (the data iterator),
``train.step`` (the dispatch of the jitted step), ``train.readback`` (the
metrics' device-to-host copy, where the host waits for the device) and
``train.checkpoint`` (a save). ``--trace-dir DIR`` profiles the three steps
after the compile step into DIR (TensorBoard's profile plugin layout,
``DIR/plugins/profile/<run>/*.xplane.pb``), so a straggler or a device gap
can be put down to the data iterator, the checkpoint writer or the device on
the device trace's own clock. The train step's ops carry the program's layer
scopes (embed, attention, moe, loss, optimizer, block) and kernel names in
their op names.

``run(argv)`` is the same launcher as a library call: it returns the per-step
metrics, the compiled step and the final state (chip_smoke.py checks them).
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, List, NamedTuple


class TrainRun(NamedTuple):
    history: List[Dict[str, float]]   # per step run: step, loss, grad_norm, lr
    compiled: Any                     # the compiled train step
    compile_s: float                  # lower + compile wall seconds
    state: Any                        # final train state (on device)


def main(argv=None) -> int:
    run(argv)
    return 0


def run(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="wt103-47m-moe")
    ap.add_argument("--ffn", default=None,
                    help="swap FFN kind (sigma_moe|topk|pkm|dense)")
    ap.add_argument("--impl", default="auto",
                    help="FFN kernel lowering (FFNConfig.impl), e.g. ragged")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=2.5e-4)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x2")
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config of the arch")
    ap.add_argument("--trace-dir", default=None,
                    help="profile the three steps after the compile step "
                         "into this directory")
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="TESTING: raise at this step to exercise restart")
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..checkpoint import CheckpointManager
    from ..configs import OptimizerConfig, get_config, reduced
    from ..data import DataIterator, make_dataset
    from ..models import build_model
    from ..runtime.monitor import StragglerMonitor
    from ..runtime.steps import init_train_state, make_train_step
    from ..sharding import TRAIN_RULES, mesh_context, tree_shardings
    from .compile_cache import use_compile_cache
    from .mesh import make_mesh

    use_compile_cache()
    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dshape, ("data", "model")[: len(dshape)] if len(dshape) == 2
                     else ("pod", "data", "model"))

    cfg = reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn, impl=args.impl))
    model = build_model(cfg, remat=args.remat,
                        ep_degree=mesh.shape.get("model", 1),
                        ffn=args.ffn)
    cfg = model.cfg

    opt_cfg = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                              grad_accum=args.grad_accum,
                              grad_compression=args.grad_compression)
    # Mesh-aware step: with a 'pod' axis of size > 1 and compression on, the
    # expert-gradient all-reduce over the DCN tier goes through per-pod
    # error-feedback quantization (runtime/steps.py pod tier) instead of a
    # host-local roundtrip.
    pod = mesh.shape["pod"] if "pod" in mesh.axis_names else 1
    if pod > 1 and args.grad_compression != "none":
        print(f"[mesh] pod tier active: {args.grad_compression} error-feedback "
              f"compression on the expert subtree across pod={pod}", flush=True)
    train_step = make_train_step(model, opt_cfg, grad_accum=args.grad_accum,
                                 mesh=mesh)

    ds = make_dataset(args.data, cfg.vocab_size)
    it = DataIterator(ds, args.batch, args.seq + 1, seed=args.seed)

    mgr = CheckpointManager(args.ckpt_dir, keep=args.keep)
    mon = StragglerMonitor(on_straggler=lambda s, dt, mu: print(
        f"[straggler] step {s}: {dt:.3f}s vs mean {mu:.3f}s", flush=True))

    with mesh_context(mesh):
        key = jax.random.PRNGKey(args.seed)
        state = init_train_state(model, key, opt_cfg, use_mems=bool(cfg.xl_memory),
                                 batch=args.batch, pod=pod)
        shardings = tree_shardings(state, mesh, TRAIN_RULES)
        state = jax.device_put(state, shardings)

        start_step = 0
        if args.resume:
            restored, extra = mgr.restore(state, shardings=shardings)
            if restored is not None:
                state = restored
                start_step = int(extra["step"])
                it.restore(extra["data"])
                print(f"[resume] restored step {start_step}", flush=True)

        # Pinning the state's output shardings to its input shardings keeps
        # every step on the rules' layout and on one executable.
        step_fn = jax.jit(train_step, donate_argnums=(0,),
                          out_shardings=(shardings, NamedSharding(mesh, P())))
        rng = jax.random.PRNGKey(args.seed + 1)
        compiled, compile_s, history = None, 0.0, []
        span = jax.profiler.TraceAnnotation
        tracing = False

        t_start = time.time()
        try:
            for step in range(start_step, args.steps):
                if step == args.fail_at_step:
                    raise RuntimeError(f"injected failure at step {step}")
                if args.trace_dir and step == start_step + 1:
                    jax.profiler.start_trace(args.trace_dir)
                    tracing = True
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    with span("train.batch"):
                        batch = {k: jnp.asarray(v)
                                 for k, v in it.next().items()}
                    if compiled is None:
                        # jit reuses this executable on the first call
                        t0 = time.time()
                        compiled = step_fn.lower(state, batch, rng).compile()
                        compile_s = time.time() - t0
                        print(f"[compile] train step {compile_s:.1f}s",
                              flush=True)
                    mon.start()
                    with span("train.step"):
                        state, metrics = step_fn(state, batch, rng)
                    with span("train.readback"):
                        m = {k: float(metrics[k])
                             for k in ("loss", "grad_norm", "lr",
                                       "moe_rows_held", "moe_tile_share")
                             if k in metrics}
                    history.append(dict(m, step=step))
                    dt = mon.stop(step)
                    if step % args.log_every == 0 or step == args.steps - 1:
                        held = (f" rows_held {m['moe_rows_held']:.0f}"
                                f" tile_share {m['moe_tile_share']:.3f}"
                                if "moe_rows_held" in m else "")
                        print(f"step {step:5d} loss {m['loss']:.4f} "
                              f"lr {m['lr']:.2e} gnorm {m['grad_norm']:.3f}"
                              f"{held} {dt:.3f}s", flush=True)
                    if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                        with span("train.checkpoint"):
                            mgr.save(step + 1, state,
                                     extra={"data": it.state()})
                if tracing and step == start_step + 3:
                    jax.profiler.stop_trace()
                    tracing = False
        except BaseException:
            # Preemption/crash path: an async save started before the failure
            # must still commit, or "loses at most ckpt_every steps" is a lie —
            # the daemon writer thread dies with the process mid-write.
            mgr.wait()
            raise
        finally:
            if tracing:
                jax.profiler.stop_trace()
        mgr.save(args.steps, state, extra={"data": it.state()}, blocking=True)
        mgr.wait()
        total = time.time() - t_start
        print(f"[done] {args.steps - start_step} steps in {total:.1f}s "
              f"({(args.steps - start_step) / max(total, 1e-9):.2f} it/s); "
              f"stragglers={len(mon.flagged)}", flush=True)
    return TrainRun(history, compiled, compile_s, state)


if __name__ == "__main__":
    sys.exit(main())
