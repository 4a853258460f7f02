"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure (cycle-accurate cost model on real
quantized weights) and the Pallas kernel metrics.  Output format:
name,us_per_call,derived (CSV).
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    failures = 0
    from benchmarks import bench_kernels, bench_paper_tables
    sections = [("paper_tables", bench_paper_tables.run),
                ("kernels", bench_kernels.run)]
    print("name,us_per_call,derived")
    for name, fn in sections:
        try:
            for row in fn():
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
