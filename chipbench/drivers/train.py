"""Training driver: the launcher's train step, timed over a window of steps.

Set-up builds one object, the jitted step with its state, as
``repro.launch.train`` builds it (``build_model``, ``make_train_step``,
``tree_shardings`` under ``TRAIN_RULES``, jit with donation and pinned
out-shardings), from weights and batches the harness makes from the seed.
The first steps run through that same call and feed in set-up: step 1
compiles, and steps 1-3 give what the check compares. The window then runs
steps until ``--seconds`` have passed, reading each step's metrics back as
the launcher does. ``train_tokens_per_s`` is every token predicted in the
window's steps over the window's host-clock seconds.

Check (after the window, with the program's state freed): the configuration's
plain float32 reference follows the same first three steps from the same
weights and batches. Read, and compared where ``limits/<cell>.json`` gives a
limit:
``loss_gap``    worst relative gap of the three steps' losses;
``grad_gap``    worst leaf's gap between the norms of the first gradient as
                the optimizer got it (its first moment after step 1 over
                1 - b1) and the reference's, over the larger of that leaf's
                reference norm and the median leaf's;
``update_gap``  the same for each leaf's change over the three steps, leaving
                out leaves whose reference gradient is under a thousandth of
                the median leaf's (they move by round-off alone);
``layer1_gap``  the hidden state that the first layer (xl_rel attention and
                sigma-MoE) hands on, as step 1 leaves it in the XL memory of
                layer 1, compared token by token: the median over tokens of
                |got - want| / |want|.
Gaps of norms are blind to rounding noise that does not bias: it moves a
norm only to second order. A token's own gap is not, and the median over
tokens passes over the few that near-tie top-k routing sends elsewhere.
Deeper layers add such flips layer by layer, so only the first layer's gap
tells the precision apart; every layer's is logged.

With ``run.test["control"]`` (set by ``chipbench/control.py`` and the tests,
never by the benchmark's runs) the reference computed in that lower
precision is put in the program's place for the check: its numbers are
compared against the same limits, and the program's are kept in the facts.
"""
from __future__ import annotations

import gc
import time

import numpy as np

CHECKED_STEPS = 3


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(t)]))(tree)


def leaf_gaps(got, want, keep):
    """Per kept leaf: |got - want| over the larger of that leaf's reference
    norm and the median kept leaf's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    base = np.maximum(want, np.median(want[keep]))
    return (np.abs(got - want) / base)[keep]


def row_gaps(got, want):
    """Per layer: the median over its rows of |got - want| / |want|, for
    (layers, ..., width) arrays."""
    got = np.asarray(got, np.float32).reshape(got.shape[0], -1, got.shape[-1])
    want = np.asarray(want, np.float32).reshape(got.shape)
    gap = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    return np.median(gap, axis=1)


def readings(got, ref):
    """The numbers of the module docstring, by name."""
    loss_p, loss_r = np.asarray(got["losses"]), np.asarray(ref["losses"])
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    every = np.ones(g_ref.shape, bool)
    moving = g_ref >= 1e-3 * np.median(g_ref)
    out = {
        "loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r))),
        "grad_gap": float(np.max(leaf_gaps(got["grad_norms"], g_ref,
                                           every))),
        "update_gap": float(np.max(leaf_gaps(
            got["update_norms"], ref["update_norms"], moving))),
    }
    if got.get("first_mems") is not None:
        out["layer1_gap"] = float(row_gaps(got["first_mems"],
                                           ref["first_mems"])[1])
    return out


def build(run):
    """Model, jitted step, state and batches, as the launcher builds them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench import faults
    from chipbench.model import make_weights, program_config
    from repro.configs import OptimizerConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.models.stack import init_mems
    from repro.optim import adamw_init
    from repro.runtime.steps import make_train_step
    from repro.sharding import TRAIN_RULES, tree_shardings

    t = run.config["train"]
    traffic = run.traffic
    cfg = program_config(run)
    model = build_model(cfg, remat=t["remat"])
    opt_cfg = OptimizerConfig(**t["optimizer"])
    mesh = make_mesh((1, 1), ("data", "model"))
    train_step = faults.wrap_train_step(
        make_train_step(model, opt_cfg, mesh=mesh), run.test.get("fault"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_weights(shapes, run.seed, run.config.get("init_std"))
    state = {"params": params, "opt": adamw_init(params)}
    if cfg.xl_memory:
        state["mems"] = init_mems(cfg, traffic["batch"], model.dtype)
    shardings = tree_shardings(state, mesh, TRAIN_RULES)
    state = jax.device_put(state, shardings)
    step_fn = jax.jit(train_step, donate_argnums=(0,),
                      out_shardings=(shardings, NamedSharding(mesh, P())))
    batches = run.generator().make(traffic, run.seed, cfg.vocab_size)
    return cfg, model, opt_cfg, mesh, step_fn, state, batches


def run(run):
    import jax

    from chipbench.harness import Check, Outcome, jax_key, log
    from chipbench.model import model_dict
    from repro.sharding import mesh_context

    cfg, model, opt_cfg, mesh, step_fn, state, batches = build(run)
    span = jax.profiler.TraceAnnotation
    shapes = jax.eval_shape(lambda s: s, state["params"])
    first_batches = np.asarray(batches[:CHECKED_STEPS])
    traffic = run.traffic
    tokens_per_step = traffic["batch"] * traffic["seq"]
    n_pool = batches.shape[0]
    rng = jax_key(run.seed, 3)

    def feed(i):
        return {"tokens": batches[i % n_pool]}

    with mesh_context(mesh):
        p0 = jax.jit(lambda p: jax.tree_util.tree_map(lambda x: x * 1, p))(
            state["params"])
        losses, mu_norms = [], None
        for i in range(CHECKED_STEPS):
            state, metrics = step_fn(state, feed(i), rng)
            losses.append(float(metrics["loss"]))
            if i == 0:
                mu_norms = np.asarray(_leaf_norms(state["opt"].mu))
                mems = (jax.tree_util.tree_leaves(
                    jax.device_get(state["mems"]))[0]
                    if "mems" in state else None)
        update_norms = np.asarray(_leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, state["params"], p0)))
        del p0
        setup_s = time.perf_counter() - run.t_start

        # ----------------------------------------------------------- window
        step, steps_in_window, bad = CHECKED_STEPS, 0, 0
        trace_at = traffic.get("trace_after_steps", 2)
        trace_steps = traffic.get("trace_steps", 3)
        step_ends = []
        t0 = time.perf_counter()
        while True:
            if steps_in_window == trace_at:
                run.tracer.start()
            with span("train.step"):
                state, metrics = step_fn(state, feed(step), rng)
            with span("train.readback"):
                m = {k: float(metrics[k])
                     for k in ("loss", "grad_norm", "lr")}
            bad += not np.isfinite(m["loss"])
            step += 1
            steps_in_window += 1
            if steps_in_window == trace_at + trace_steps:
                run.tracer.stop()
            step_ends.append(time.perf_counter())
            if step_ends[-1] - t0 >= run.seconds:
                break
        window_s = time.perf_counter() - t0
        run.tracer.stop()
    step_s = np.diff([t0] + step_ends)

    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    del state, step_fn, batches, metrics, model
    gc.collect()
    slow = np.argsort(step_s)[::-1][:3]
    log(f"setup {setup_s:.1f}s, window {window_s:.1f}s ({steps_in_window} "
        f"steps, median {np.median(step_s):.4f}s, slowest "
        + ", ".join(f"#{i} {step_s[i]:.4f}s" for i in slow)
        + f"), peak {peak}")
    t_ref = time.perf_counter()

    prog = {"losses": losses, "grad_norms": mu_norms / (1.0 - opt_cfg.b1),
            "update_norms": update_norms, "first_mems": mems}
    reference = run.reference()
    args = (model_dict(run, cfg), run.config["train"], shapes, first_batches,
            run.seed, run.config.get("init_std"))
    ref = reference.train_reference(*args)
    read = prog_read = readings(prog, ref)
    log("readings " + ", ".join(f"{k} {v!r}" for k, v in read.items()))
    if mems is not None:
        log("gap by layer " + " ".join(f"{g:.4g}" for g in row_gaps(
            prog["first_mems"], ref["first_mems"])))
    log(f"reference {time.perf_counter() - t_ref:.1f}s, process peak "
        f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    control = run.test.get("control")
    if control:
        ctrl = reference.train_reference(*args, precision=control)
        read = readings(ctrl, ref)
        log(f"control {control} readings "
            + ", ".join(f"{k} {v!r}" for k, v in read.items()))
        if mems is not None:
            log("control gap by layer " + " ".join(f"{g:.4g}" for g in
                                                   row_gaps(ctrl["first_mems"],
                                                            ref["first_mems"])))
    checks = [Check(k, v, run.limits[k]) for k, v in read.items()
              if k in run.limits]
    facts = {"steps": steps_in_window, "window_s": window_s,
             "traced_steps": min(trace_steps,
                                 max(steps_in_window - trace_at, 0)),
             "tokens_per_step": tokens_per_step, "model": model_dict(run, cfg),
             "traffic": traffic, "program_readings": prog_read,
             "readings": read}
    return Outcome(
        setup_s=setup_s,
        metrics={"train_tokens_per_s":
                 steps_in_window * tokens_per_step / window_s},
        checks=checks, attempted=steps_in_window, failed=int(bad),
        memory_peak_bytes=peak, facts=facts)
