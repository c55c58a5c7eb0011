"""What a lowered program computes, with every name taken off: the ops of
its StableHLO text (no debug locations, so no scope names), each line with
its SSA value names and function names blanked, and a Pallas call's
serialized kernel body too (it carries its source's line numbers). Two
programs that differ only in op names give the same fingerprint.

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/lowered_ops.py DIR

writes the fingerprints of the ``wt103-262m-moe`` train steps that the
tests compare (``wt103_train_step_text``, on the CPU and for one chip of a
described v5e) to DIR, as ``tests/data`` keeps them.
"""
from __future__ import annotations

import collections
import hashlib
import json
import re
import sys

_SSA = re.compile(r"%[\w#.:-]+")
_FUNC = re.compile(r"@[\w.$-]+")
_BODY = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')
_OP = re.compile(r"^(?:%\S*(?:, %\S*)* = )?\"?([a-z_]+\.[a-z_.]+)")


def fingerprint(text: str) -> dict:
    """{"ops": op name -> count, "sha256": hash of the sorted op lines}."""
    lines = []
    ops = collections.Counter()
    for raw in text.splitlines():
        line = raw.strip()
        m = _OP.match(line)
        if not m:
            continue
        ops[m.group(1)] += 1
        lines.append(_FUNC.sub("@f", _SSA.sub("%", _BODY.sub(
            "backend_config", line))))
    digest = hashlib.sha256("\n".join(sorted(lines)).encode()).hexdigest()
    return {"ops": dict(sorted(ops.items())), "sha256": digest}


def wt103_train_step_text(device=None) -> str:
    """The lowered text of the wt103-262m-moe train step as the launcher
    jits it: full remat, batch 2 x 33 tokens with XL memory. Without
    ``device`` the reduced config on the CPU with the default kernel
    lowering; on a (described) TPU ``device``, 2 layers at the published
    widths through the fused Pallas kernels."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.configs import OptimizerConfig, get_config, reduced
    from repro.models import build_model
    from repro.models.stack import init_mems
    from repro.optim import adamw_init
    from repro.runtime.steps import make_train_step

    if device is None:
        cfg = reduced("wt103-262m-moe")
    else:
        cfg = get_config("wt103-262m-moe").override(n_layers=2, xl_memory=128,
                                                    dropout=0.0)
        cfg = cfg.with_ffn(dataclasses.replace(
            cfg.ffn, impl="pallas_fused", expert_dropout=0.0))
    model = build_model(cfg, remat="full")
    step = make_train_step(model, OptimizerConfig())
    state = jax.eval_shape(lambda k: (lambda p: {
        "params": p, "opt": adamw_init(p),
        "mems": init_mems(cfg, 2, model.dtype)})(model.init(k)),
        jax.random.PRNGKey(0))
    args = (state, {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    fn = jax.jit(step, donate_argnums=(0,))
    if device is None:
        return fn.lower(*args).as_text()
    sh = SingleDeviceSharding(device)
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), args)
    # the kernels lower for the chip only where the repo sees a TPU backend
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        return fn.lower(*args).as_text()
    finally:
        jax.default_backend = backend


def v5e_device():
    """One chip of a described v5e:2x2 (nothing runs on it)."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices[0]


def main(out_dir: str) -> None:
    import os
    for name, device in (("wt103_train_step_cpu", None),
                         ("wt103_train_step_v5e", v5e_device())):
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(fingerprint(wt103_train_step_text(device)), f,
                      indent=1)
            f.write("\n")


if __name__ == "__main__":
    main(sys.argv[1])
