"""Pieces every cell shares: the chip check, the peak table, the compile
counter, quantiles, host spans and loading files by name.

Nothing here imports the program (``repro``): this is the yardstick side.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, Iterable, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a Python file by path (names of configurations and metrics
    may hold '-' and '.', which ``import`` cannot spell)."""
    name = "bench_chip_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ device

def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip, keyed by JAX's ``device_kind``.  A
    device that is not in the table is an error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]


def require_chip(chips: int) -> Dict[str, Any]:
    """The device stamp of this run; raises :class:`NoChip` unless JAX's
    default backend is a TPU with at least ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devs[0].platform!r}; the "
                     f"benchmark measures on a TPU only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    peaks_for(devs[0].device_kind)
    return device_stamp(chips)


def device_stamp(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says).  Every program
    is kept, the small eager ones too, so a second run compiles nothing."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX compilations (backend compiles and persistent-cache
    loads) so a run can show that none fell inside its window."""

    _EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
               "/jax/compilation_cache/cache_hits": "cache_loads",
               "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.counts = {v: 0 for v in self._EVENTS.values()}
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on)

    def _on(self, name: str, *args, **kwargs) -> None:
        key = self._EVENTS.get(name)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)

    def since(self, snap: Dict[str, int]) -> Dict[str, int]:
        return {k: self.counts[k] - snap.get(k, 0) for k in self.counts}


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# ------------------------------------------------------------ numbers

def percentile(values: Iterable[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between
    order statistics; +inf entries count as beyond every finite value."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Clock:
    """Seconds since the process started (``run.py`` sets the origin)."""

    origin = time.perf_counter()

    @classmethod
    def now(cls) -> float:
        return time.perf_counter() - cls.origin
