#!/usr/bin/env python3
"""Record ``chipbench/testdata/train_scopes.xplane.pb`` on a TPU: a profile
of two train steps of wt103-262m-moe cut to 2 layers (published widths, XL
memory 128, batch 4 x 128 predicted tokens), built as the train driver
builds its step (sort dispatch on the fused Pallas kernels, full remat, a
1x1 mesh), after one step that compiles. ``chipbench/tests/test_scopes.py``
checks the scope reduction against it. The ``/host:metadata`` plane (the
run's HLO protos, 2 MB, which the reduction does not read) is left out.

    python3 chipbench/tests/record_train_scopes.py --out <file.xplane.pb>
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
DROPPED = ("/host:metadata",)


def drop_planes(buf: bytes, names=DROPPED) -> bytes:
    """The XSpace ``buf`` without its planes (field 1) named in ``names``;
    every top-level field of an XSpace is length-delimited."""
    from chipbench.scopes import _message, _str, _varint
    out, pos = bytearray(), 0
    while pos < len(buf):
        start = pos
        key, pos = _varint(buf, pos)
        size, pos = _varint(buf, pos)
        end = pos + size
        if key >> 3 != 1 or _str(buf, _message(buf, (pos, end)), 2) \
                not in names:
            out += buf[start:end]
        pos = end
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import OptimizerConfig, get_config
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.runtime.steps import init_train_state, make_train_step
    from repro.sharding import TRAIN_RULES, mesh_context, tree_shardings

    if jax.devices()[0].platform != "tpu":
        print("record_train_scopes: no TPU", file=sys.stderr)
        return 2
    batch, seq = 4, 128
    cfg = get_config("wt103-262m-moe").override(n_layers=2, xl_memory=128,
                                                dropout=0.0)
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn, expert_dropout=0.0))
    model = build_model(cfg, remat="full")
    mesh = make_mesh((1, 1), ("data", "model"))
    step = make_train_step(model, OptimizerConfig(), mesh=mesh)
    with mesh_context(mesh):
        state = init_train_state(model, jax.random.PRNGKey(0),
                                 OptimizerConfig(), use_mems=True,
                                 batch=batch)
        shardings = tree_shardings(state, mesh, TRAIN_RULES)
        state = jax.device_put(state, shardings)
        step_fn = jax.jit(step, donate_argnums=(0,),
                          out_shardings=(shardings, NamedSharding(mesh, P())))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                    0, cfg.vocab_size, jnp.int32)
        rng = jax.random.PRNGKey(2)
        state, m = step_fn(state, {"tokens": tokens}, rng)
        float(m["loss"])
        tmp = tempfile.mkdtemp()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for _ in range(2):
            state, m = step_fn(state, {"tokens": tokens}, rng)
            float(m["loss"])
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(path, "rb") as f, open(args.out, "wb") as g:
        g.write(drop_planes(f.read()))
    shutil.rmtree(tmp)
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
