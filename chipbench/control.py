#!/usr/bin/env python3
"""Readings that the limits in ``chipbench/limits`` are set from. Not run by
the benchmark's own runs.

    python3 chipbench/control.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 3] [--fault <name>]

For each seed it makes one run of the cell through its driver (a window of
one second; the check's numbers are computed as in a benchmark run) and
prints the program's readings. For the first ``--control-seeds`` seeds the
control is put in the program's place for the check: the plain reference
computed in float8 e4m3, the precision below the bfloat16 the configuration
computes in. ``--fault`` plants one of
``chipbench/faults.py``'s faults under the timed path in every seed's run
instead. Each line gives the numbers the check compared, their limits and
the verdict ``correct`` that a benchmark run would print. One JSON object per
seed on stdout; everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", default=None,
                    help="plant this fault (chipbench/faults.py) in every "
                         "seed's run instead of reading the control")
    args = ap.parse_args(argv)
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 2
    from chipbench import harness
    from repro.kernels import autotune
    autotune.enable(False)

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        test = {}
        if args.fault:
            test["fault"] = args.fault
        elif i < args.control_seeds:
            test["control"] = "fp8"
        run = harness.open_run(args.workload, seed, 1.0, False, test=test)
        out = run.module("drivers", run.config["driver"]).run(run)
        line = {"seed": seed, "control": test.get("control"),
                "fault": test.get("fault"),
                "correct": harness.verdict(out.checks),
                "checks": {c.name: {"value": c.value, "limit": c.limit}
                           for c in out.checks},
                "readings": out.facts["readings"],
                "program": out.facts["program_readings"],
                "metrics": out.metrics, "setup_s": out.setup_s,
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
