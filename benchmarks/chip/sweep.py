#!/usr/bin/env python3
"""Find the knee of an open-loop LM cell: the highest offered rate at which
the queue does not grow over the window.

    python3 benchmarks/chip/sweep.py --workload smollm-360m.chat \\
        --rates 3 4 5 6 7 8 --seconds 20 --seed 11

One process: the engine is built and warmed once, then each rate gets a
ramp and a window of the cell's own mix at that rate; everything left over
is cancelled before the next rate.  For each rate it prints the requests
still queued at the window's start and end, TTFT p90 and TPOT p90.  The
cell keeps a fixed rate (``traffic/<mix>.json``); this tool is how it was
chosen.  TPU only, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import common, run, traffic  # noqa: E402
from benchmarks.chip.drivers import lm_serving as lm  # noqa: E402

common.Clock.origin = T_START


def one_rate(engine, mix, rate, seed, seconds, vocab):
    mix = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate),
               tail_s=0.0)
    reqs = traffic.lm_requests(mix, seed, vocab, seconds)
    w0, w1 = traffic.window_bounds(mix, seconds)
    loop = lm.Loop(engine, reqs)
    loop.run_until(lambda lp: lp.now() >= w0)
    queued = lambda lp: sum(1 for t in lp.all if t.handle.state == "queued")
    q0 = queued(loop)
    loop.run_until(lambda lp: lp.now() >= w1)
    q1 = queued(loop)
    win = [t for t in loop.all if t.req.phase == "window"]
    end = loop.now()
    ttft = [(min(t.first_s, end) - t.req.due_s) * 1e3 for t in win]
    tpot = [(t.last_s - t.first_s) * 1e3 / (t.n - 1) for t in win
            if t.n > 1 and t.last_s < float("inf")]
    steps = [s for s in loop.steps if w0 <= s["t0"] < w1]
    live = [len(s["contexts"]) for s in steps]
    for t in loop.all:
        t.handle.cancel()
    while engine.scheduler_step():
        pass
    return {"rate_per_s": rate, "queued_at_start": q0, "queued_at_end": q1,
            "sent": len(win), "with_first_token": sum(t.n > 0 for t in win),
            "ttft_p90_ms": common.percentile(ttft, 90),
            "tpot_p90_ms": common.percentile(tpot, 90),
            "mean_live_rows": sum(live) / max(1, len(live)),
            "steps": len(steps)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix = run.load_cell(bench, args.workload)
    try:
        common.require_chip(cell["chips"])
    except common.NoChip as e:
        common.log(f"sweep: {e}")
        return 2
    common.enable_compile_cache()
    engine = lm.build_engine(cfg, mix, lm.float_weights(cfg, args.seed))
    lm.warm_up(engine, cfg, mix)
    common.log(f"sweep: engine warm at {common.Clock.now():.1f} s")
    for rate in args.rates:
        row = one_rate(engine, mix, rate, args.seed, args.seconds,
                       cfg["model"]["vocab_size"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
