"""Benchmark harness: one module per paper table.  Prints
``name,us_per_call,derived`` CSV and persists per-table JSON under
benchmarks/results/."""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (bench_complex_tasks, bench_correlation,
                            bench_index_size, bench_kernels, bench_mc,
                            bench_optimizer, bench_sc_join, bench_union)
    suites = [
        ("table3_complex_tasks", bench_complex_tasks.main),
        ("table4_optimizer", bench_optimizer.main),
        ("fig5_sc_join", bench_sc_join.main),
        ("table5_mc", bench_mc.main),
        ("table6_union", bench_union.main),
        ("table7_correlation", bench_correlation.main),
        ("table8_index_size", bench_index_size.main),
        ("kernels", bench_kernels.main),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        t0 = time.time()
        try:
            fn()
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
