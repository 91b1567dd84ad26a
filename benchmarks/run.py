"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table3_4   # one asset
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI-speed smoke subset
    REPRO_BENCH_FAST=1 ...                             # small sizes, any suite

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py) and writes
each suite's rows to ``BENCH_<suite>.json`` in the working directory — the
machine-readable artifact CI uploads so the perf trajectory (engine QPS,
pq-vs-f32 bytes/recall, serving throughput) is tracked across PRs.
"""
import os
import sys

SMOKE_SUITES = [
    "engine", "kernels", "service", "distributed", "store", "obs", "fault",
    "tuner", "perf",
]


def main() -> None:
    args = sys.argv[1:]
    if "--smoke" in args:
        args = [a for a in args if a != "--smoke"]
        os.environ["REPRO_BENCH_FAST"] = "1"
        args = args or SMOKE_SUITES

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_distributed, bench_engine, bench_fault, bench_fig4_5, bench_fig6,
        bench_fig7, bench_kernels, bench_perf, bench_service, bench_store,
        bench_table3_4, bench_table5, bench_tuner, common,
    )

    suites = {
        "table3_4": bench_table3_4.main,
        "table5": bench_table5.main,
        "fig4_5": bench_fig4_5.main,
        "fig6": bench_fig6.main,
        "fig7": bench_fig7.main,
        "kernels": bench_kernels.main,
        "engine": bench_engine.main,
        "service": bench_service.main,
        "distributed": bench_distributed.main,
        "store": bench_store.main,
        "obs": bench_service.main_obs,
        "fault": bench_fault.main,
        "tuner": bench_tuner.main,
        "perf": bench_perf.main,
    }
    picks = args or list(suites)
    print("name,us_per_call,derived")
    for p in picks:
        n0 = len(common.rows())
        suites[p]()
        path = common.write_suite_json(p, common.rows()[n0:])
        print(f"# wrote {path}", flush=True)


if __name__ == '__main__':
    main()
