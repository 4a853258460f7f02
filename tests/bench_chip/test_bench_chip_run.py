"""The benchmark's entry: no chip, no numbers; and every name in
BENCHMARK.json resolves to the files the harness looks for."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_chip_small as small
from benchmarks.chip import common

ROOT = small.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         "smollm-360m.chat", "--seed", "5", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    r = _run(ROOT, "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_bare_benchmark_files_fail(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    paths has no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_every_name_resolves():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (common.HERE / "configs" / cfg["reference"]).is_file()
        assert (common.HERE / "drivers" / f"{cfg['driver']}.py").is_file()
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        assert (common.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert (common.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_follow_the_contract(key):
    names = [e["name"] for e in BENCH[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        def of(key):
            return [m["name"] for m in BENCH[key]
                    if w["name"] in m.get("workloads", [w["name"]])]
        e2e = of("end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = of("per_layer")
        assert layer
        for m in BENCH["per_layer"]:
            if m["name"] in layer:
                assert m["moves"] in e2e


def test_bounds_within_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= BENCH["run_seconds"] <= 51
