"""Benchmark aggregator -- one module per paper table/figure, plus the CVMM
hot-path micro-benchmark (bench_cvmm -> BENCH_cvmm.json). The cvmm module's
``pkm_large`` section (64k+ value PKM aggregation through the deduplicated
coalescing gather) rides the --quick subset and carries the CI-gated
``dma_descriptors.batching_factor`` coalescing signal.

    PYTHONPATH=src python -m benchmarks.run [--steps N] [--only tableX]
    PYTHONPATH=src python -m benchmarks.run --quick    # smoke: cvmm + fig2
    PYTHONPATH=src python -m benchmarks.run --quick --tune  # pre-warm tile cache

``--tune`` turns on the kernel autotuner (kernels/autotune.py) for this run:
tile choices come from the persistent on-disk cache, micro-benchmarking any
missing (kernel, shape, dtype, backend) keys once and storing the winners, so
a subsequent run — bench or training — is a pure cache hit. Without it the
tuner stays in zero-cost heuristic mode (the CI default).

Prints ``name,us_per_call,derived`` CSV rows per benchmark.
"""
import argparse
import sys
import time

QUICK = ("cvmm", "fig2", "serve")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="fast smoke subset (%s) with reduced iters" %
                         ",".join(QUICK))
    ap.add_argument("--tune", action="store_true",
                    help="enable the kernel autotuner: micro-bench uncached "
                         "tile candidates and persist winners to the on-disk "
                         "cache (pre-warms it for later runs)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.tune:
        from repro.kernels import autotune
        autotune.enable(True)
        print(f"# autotune on: cache={autotune.cache_path()}", flush=True)

    from . import (bench_cvmm, bench_serve, fig1_active_channels,
                   fig2_exec_time, fig3_expert_usage, table1_topk,
                   table2_pkm, table3_sigma_moe, table4_ablations)
    mods = {
        "cvmm": lambda: bench_cvmm.run(iters=3 if args.quick else 10),
        "serve": lambda: bench_serve.run(quick=args.quick),
        "table1": lambda: table1_topk.run(args.steps),
        "table2": lambda: table2_pkm.run(args.steps),
        "table3": lambda: table3_sigma_moe.run(max(args.steps, 150)),
        "table4": lambda: table4_ablations.run(max(args.steps - 20, 60)),
        "fig1": lambda: fig1_active_channels.run(args.steps),
        "fig2": lambda: fig2_exec_time.run(),
        "fig3": lambda: fig3_expert_usage.run(args.steps),
    }
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in mods.items():
        if args.only and name != args.only:
            continue
        if args.quick and name not in QUICK:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # report and continue
            failures += 1
            print(f"{name},nan,ERROR={type(e).__name__}:{e}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.tune:
        from repro.kernels import autotune
        print(f"# autotune stats: {autotune.STATS}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
