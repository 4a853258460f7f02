"""CI perf gate: compare a bench_kernels JSON against the committed baseline.

Gated metrics are the *deterministic* schedule/cycle quantities (committed
trained weights + fixed seeds), not wall clocks: ``executed_tile_dots`` and
``cycle_ratio`` are lower-is-better — a PR that makes the compacted schedule
dispatch more MXU passes, or worsens the kneaded cycle ratio, by more than
``--tolerance`` (default 10%) fails the build.  ``max_err`` is gated the
same way so kernel-accuracy regressions can't hide behind perf numbers.

Usage:
  python -m benchmarks.check_regression CURRENT.json \\
      [--baseline benchmarks/artifacts/bench_baseline.json] [--tolerance 0.10]

Regenerate the baseline (after an *intended* change, commit the diff):
  python -m benchmarks.bench_kernels --quick \\
      --json benchmarks/artifacts/bench_baseline.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Dict

BASELINE = pathlib.Path(__file__).resolve().parent / "artifacts" / \
    "bench_baseline.json"

# lower-is-better metrics the gate enforces (absolute counts and ratios —
# all reproducible bit-for-bit from committed weights).  shard_executed_max
# is the sharded sweep's critical-path load: the MXU passes the most-loaded
# device of the 4-shard partition executes — a PR that skews the N-shard
# balance (or inflates any shard's work list) by >tolerance fails even if
# the total stays flat.  shard_imbalance is the same skew as a ratio
# (max / mean shard work): the balanced-partition rows baseline it at
# ~1.0, so a packing change that un-balances an LPT row fails even when
# absolute work counts move with an intended schedule change.  The
# latency-tick metrics come from the
# serving_load_sweep's fixed Poisson trace on the virtual-launch clock:
# a scheduler change that makes requests wait more launches, or spends
# more launches on the same trace, fails the build.  failed_requests and
# retries come from serving_fault_sweep's deterministic fault plan: a
# fault-handling change that starts losing requests (baseline 0 — any
# loss fails) or needs more recovery attempts for the same injected
# faults fails too.  expert_imbalance is the moe_decode_sweep's static
# per-expert work-table skew (max / mean tile-dots across the fixed-seed
# skewed bank's experts): a kneading or bank-layout change that moves
# work between experts shifts it and fails, alongside the sweep's gated
# executed_tile_dots (runtime-masked routed work) and max_err (emulated
# expert-parallel vs all-local, baselined at exactly 0.0).
GATED = ("executed_tile_dots", "cycle_ratio", "max_err",
         "shard_executed_max", "shard_imbalance", "expert_imbalance",
         "p50_latency_ticks",
         "p95_latency_ticks", "total_ticks", "failed_requests", "retries")
# max_err floor: don't flag 1e-6-scale float noise as a "regression"
ABS_FLOOR = {"max_err": 1e-4}


def _by_name(rows) -> Dict[str, dict]:
    return {r["name"]: r.get("metrics", {}) for r in rows}


def compare(current: Dict[str, dict], baseline: Dict[str, dict],
            tolerance: float) -> list:
    failures = []
    for name, base_met in baseline.items():
        gated = {k: v for k, v in base_met.items() if k in GATED}
        if not gated:
            continue
        if name not in current:
            failures.append(f"{name}: row missing from current bench output")
            continue
        cur_met = current[name]
        for key, base_val in gated.items():
            if key not in cur_met:
                failures.append(f"{name}.{key}: metric missing")
                continue
            cur_val = float(cur_met[key])
            limit = float(base_val) * (1.0 + tolerance) + \
                ABS_FLOOR.get(key, 0.0)
            if cur_val > limit:
                failures.append(
                    f"{name}.{key}: {cur_val:.6g} exceeds baseline "
                    f"{float(base_val):.6g} by more than "
                    f"{100 * tolerance:.0f}%")
    return failures


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("current", help="bench_kernels --json output to check")
    parser.add_argument("--baseline", default=str(BASELINE))
    parser.add_argument("--tolerance", type=float, default=0.10)
    args = parser.parse_args(argv)

    with open(args.current) as f:
        current = _by_name(json.load(f))
    with open(args.baseline) as f:
        baseline = _by_name(json.load(f))

    failures = compare(current, baseline, args.tolerance)
    if failures:
        print("PERF REGRESSION vs committed baseline "
              f"({args.baseline}):", file=sys.stderr)
        for msg in failures:
            print(f"  FAIL {msg}", file=sys.stderr)
        return 1
    n = sum(1 for met in baseline.values()
            if any(k in GATED for k in met))
    print(f"perf gate OK: {n} baselined rows within "
          f"{100 * args.tolerance:.0f}% of {args.baseline}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
