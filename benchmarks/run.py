"""Benchmark entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (bench_kernels, bench_partition, bench_scale,
                   bench_shuffle_bytes, bench_speedup, bench_threshold)
    suites = [
        ("fig9_threshold", bench_threshold.main),
        ("fig8_partition", bench_partition.main),
        ("fig10_11_scale", bench_scale.main),
        ("table3_disk", bench_shuffle_bytes.main),
        ("fig6_7_speedup", bench_speedup.main),
        ("kernels", bench_kernels.main),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites:
        print(f"# suite: {name}", flush=True)
        try:
            fn()
        except Exception:
            failed += 1
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
