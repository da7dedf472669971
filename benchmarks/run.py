"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
"""

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_edge_cloud, bench_kernels,
                            bench_migration, bench_replication,
                            bench_runtime_overhead, bench_speculation,
                            bench_validation)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for mod in (bench_migration, bench_runtime_overhead, bench_edge_cloud,
                bench_replication, bench_speculation, bench_validation,
                bench_kernels):
        try:
            mod.run()
        except Exception:
            failures.append(mod.__name__)
            traceback.print_exc()
    if failures:
        print("FAILED:", ",".join(failures))
        sys.exit(1)


if __name__ == "__main__":
    main()
