#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload smollm-360m.chat --seed 7 \\
        --seconds 30 --trace 0

The cell is looked up by name in ``BENCHMARK.json``; its configuration file
names the driver (``drivers/<driver>.py``) and the plain reference beside
it, its traffic mix is ``traffic/<mix>.json``, and each per-layer metric is
read by ``metrics/<metric>.py``.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled stretch of the
window.  The last line of standard output is one JSON object; the compared
numbers and their limits are the last lines of standard error and the last
key of that object.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for: nothing here falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import common, traffic  # noqa: E402

common.Clock.origin = T_START


class Context:
    """What a driver gets: the cell's files, the seed and the run's knobs."""

    def __init__(self, cell, cfg, mix, seed, seconds, trace, counter,
                 control: bool = False):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.counter = counter
        self.control = control      # also read the control (calibrate.py)
        self.reference = common.load_module(
            common.HERE / "configs" / cfg["reference"])
        self._scratch = []

    def scratch(self, name: str) -> str:
        d = tempfile.mkdtemp(prefix=f"chipbench_{name}_")
        self._scratch.append(d)
        return d

    def cleanup(self) -> None:
        for d in self._scratch:
            shutil.rmtree(d, ignore_errors=True)


def load_cell(bench, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = common.load_json(ROOT / entry["file"])
    return cell, cfg, traffic.load_mix(cell["traffic"])


def metrics_of(bench, cell, key: str):
    return [m for m in bench[key]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def per_layer_values(bench, cell, record, peaks):
    out = {}
    for m in metrics_of(bench, cell, "per_layer"):
        reader = common.load_module(common.HERE / "metrics" /
                                    f"{m['name']}.py")
        value = reader.read(record, cell=cell, peaks=peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, require_tpu: bool = True, overrides=None,
         cache: bool = True) -> int:
    """``require_tpu=False``, ``overrides`` (a function of the cell's
    configuration and mix) and ``cache=False`` let a CPU test drive a whole
    run at a small size."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix = load_cell(bench, args.workload)
    if overrides:
        cfg, mix = overrides(cfg, mix)
    try:
        stamp = (common.require_chip(cell["chips"]) if require_tpu
                 else common.device_stamp(cell["chips"]))
    except common.NoChip as e:
        common.log(f"run: {e}")
        return 2
    where = common.enable_compile_cache() if cache else None
    held = len(os.listdir(where)) if where and os.path.isdir(where) else 0
    common.log(f"run: {cell['name']} seed {args.seed} on {stamp['kind']} "
               f"x{stamp['count']}; compile cache {where} ({held} entries)")
    counter = common.CompileCounter()
    ctx = Context(cell, cfg, mix, args.seed, args.seconds, bool(args.trace),
                  counter)
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{cfg['driver']}")
    try:
        res = driver.run(ctx)
        device = dict(stamp, memory_peak_bytes=res["memory_peak_bytes"])
        line = {"correct": res["correct"], "attempted": res["attempted"],
                "failed": res["failed"]}
        if args.trace:
            record = res["record"]
            summary = record["summary"]
            peaks = (common.peaks_for(stamp["kind"]) if require_tpu
                     else None)
            line["metrics"] = per_layer_values(bench, cell, record, peaks)
            if summary is not None and summary.has_device:
                device.update(busy_s=summary.busy_s,
                              window_s=summary.window_s)
                line["breakdown"] = {"device_ops": summary.top_ops(),
                                     "idle_gaps": summary.idle_by_span()}
        else:
            line["metrics"] = {
                m["name"]: {"value": res["e2e"][m["name"]],
                            "unit": m["unit"]}
                for m in metrics_of(bench, cell, "end_to_end")}
        line["device"] = device
        line["checks"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in res["checks"].items()}
    finally:
        ctx.cleanup()
    common.log(f"run: compilations inside the window "
               f"{res['compiles_in_window']}; total {time.perf_counter() - T_START:.1f} s")
    for k, c in line["checks"].items():
        common.log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
