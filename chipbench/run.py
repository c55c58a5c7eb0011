#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name (see chipbench/harness.py),
makes the weights and inputs from ``--seed``, warms up every shape the cell
uses (set-up), measures for ``--seconds``, frees the program's state, checks
what the timed path produced against the plain float32 reference, and prints
one JSON object as the last line of stdout. With ``--trace 1`` the window is
profiled and the line carries the per-layer metrics instead of the end-to-end
ones. Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness

    run = harness.open_run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU found (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < run.workload["chips"]:
        print(f"chipbench: {run.workload['name']} needs "
              f"{run.workload['chips']} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    from repro.kernels import autotune
    autotune.enable(False)          # tiles from the heuristic, no cache file

    result = harness.execute(run)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
