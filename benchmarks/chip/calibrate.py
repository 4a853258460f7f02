#!/usr/bin/env python3
"""Readings for setting a cell's correctness limit: for each seed, one run
of the cell (as ``run.py --trace 0`` makes it) that reports the compared
number of the program and of the control, the plain reference computed in
the precision below the configuration's (the configuration file names it).

    python3 benchmarks/chip/calibrate.py --workload smollm-360m.chat \\
        --seconds 10 --seeds 1 2 3

All seeds in one process, so programs compile once.  Prints one JSON line
per seed.  The benchmark's own runs never read the control.  TPU only.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import common, run  # noqa: E402

common.Clock.origin = T_START


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix = run.load_cell(bench, args.workload)
    try:
        common.require_chip(cell["chips"])
    except common.NoChip as e:
        common.log(f"calibrate: {e}")
        return 2
    common.enable_compile_cache()
    counter = common.CompileCounter()
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{cfg['driver']}")
    for seed in args.seeds:
        common.Clock.origin = time.perf_counter()
        ctx = run.Context(cell, cfg, mix, seed, args.seconds, False, counter,
                          control=True)
        res = driver.run(ctx)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: v for k, (v, _) in res["checks"].items()},
            "control": res["control"], "e2e": res["e2e"],
            "memory_peak_bytes": res["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
