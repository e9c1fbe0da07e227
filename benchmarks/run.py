"""Benchmark driver — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        bench_accuracy,
        bench_distributed,
        bench_faults,
        bench_iterations,
        bench_scaling,
        bench_variants,
    )

    sections = [
        ("Fig1/2 variant speedups", bench_variants),
        ("Fig3/4 thread scaling", bench_scaling),
        ("Fig5/6 L1 accuracy", bench_accuracy),
        ("Fig7 iterations", bench_iterations),
        ("Fig8/9 sleep+failure", bench_faults),
        ("stale-sync distributed", bench_distributed),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for title, mod in sections:
        print(f"# --- {title} ---")
        try:
            for row in mod.main():
                print(row)
        except Exception:
            failed += 1
            print(f"# SECTION FAILED: {title}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
