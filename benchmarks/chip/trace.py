"""Profiler capture and the reduction from a trace to device numbers.

What a TPU trace holds (read by hand from one of this benchmark's traces
on a TPU v5 lite, jax 0.9): a plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per program run, named ``jit_<fn>(<hash>)``)
and ``XLA Ops`` (one event per HLO instruction run, named by its HLO text,
``%name = shape op(...), ...``; a ``while`` holds the ops of its body, so
events nest strictly), a line ``Async XLA Ops`` with the async copies
(``%copy-start``, spanning the transfer, which overlaps the ops that run
meanwhile), and a plane ``/host:CPU`` with a line per host thread.  The
host spans (``TraceAnnotation``) sit on the line of the thread that opened
them, named after it (``python3`` with the Python tracer off, as here), so
the spans are read from the host line that holds the traced window's span.
Host and device events share one clock.

Match rules (PERF.md repeats them):

* the SAC kernel is every op whose HLO text has
  ``custom_call_target="tpu_custom_call"`` (the only Mosaic kernel these
  cells run; the program gives it no stable name yet);
* a program step is a module event whose name starts with ``jit_<fn>(``;
* an op belongs to the step whose module event contains its start;
* async ops (``Async XLA Ops``, and any ``*-start`` / ``*-done`` op) are
  left out: a transfer in flight is not the core at work.

Busy time is the union of op intervals inside the traced window; an idle
gap is a stretch of the window with no op running, labelled by the
innermost host span open at its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import shutil
from typing import Callable, Dict, List, Optional, Tuple

SAC_MARK = 'custom_call_target="tpu_custom_call"'
# "%name = dtype[dims]{layout} opcode(" -> name, dtype[dims], opcode
_HLO = re.compile(r"^(%\S+) = ([a-z0-9]+\[[0-9,]*\])(?:\{[^}]*\})? ([\w-]+)\(")
# the opcode of any instruction, tuple-shaped ones too
_OPCODE = re.compile(r"^%\S+ = .*?[\s)}\]]([a-z][\w-]*)\(")

Interval = Tuple[int, int]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the body into ``log_dir`` (host Python tracing off: the
    spans are the benchmark's own)."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return found[0] if found else None


class Op:
    __slots__ = ("name", "start", "end", "self_ns")

    def __init__(self, name: str, start: int, end: int):
        self.name, self.start, self.end = name, start, end
        self.self_ns = end - start

    @property
    def label(self) -> str:
        """The instruction's name, output shape and opcode without layouts
        (``%fusion.12 bf16[64,960] fusion``); the SAC kernel's is marked."""
        m = _HLO.match(self.name)
        text = " ".join(m.groups()) if m else self.name.split(" = ", 1)[0]
        return ("sac_matmul_kernel " + text) if SAC_MARK in self.name else text


class Trace:
    """Device ops and modules per chip, and host spans, from one capture."""

    def __init__(self, devices: Dict[str, Dict[str, List[Op]]],
                 spans: List[Op]):
        self.devices = devices          # plane -> {"ops": [...], "modules": [...]}
        self.spans = sorted(spans, key=lambda s: (s.start, -s.end))
        for dev in devices.values():
            _self_times(dev["ops"])

    @classmethod
    def from_file(cls, path: str, span_name: str) -> "Trace":
        """Device ops from every TPU plane; host spans from the host line
        that holds an event named ``span_name``."""
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices: Dict[str, Dict[str, List[Op]]] = {}
        spans: List[Op] = []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {"XLA Ops": "ops",
                           "XLA Modules": "modules"}.get(line.name)
                    if key:
                        dev[key] = [Op(e.name, int(e.start_ns),
                                       int(e.start_ns + e.duration_ns))
                                    for e in line.events
                                    if not is_async(e.name)]
                devices[plane.name] = dev
            elif plane.name.startswith("/host:") and not spans:
                for line in plane.lines:
                    events = list(line.events)
                    if any(e.name == span_name for e in events):
                        spans = [Op(e.name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns))
                                 for e in events]
                        break
        return cls(devices, spans)

    def span_bounds(self, name: str) -> Optional[Interval]:
        for s in self.spans:
            if s.name == name:
                return s.start, s.end
        return None

    def span_at(self, t: int) -> str:
        """The innermost host span open at ``t``."""
        if not hasattr(self, "_seg_t"):
            segs: List[Tuple[int, Optional[str]]] = []
            stack: List[Op] = []
            for s in self.spans:
                while stack and stack[-1].end <= s.start:
                    top = stack.pop()
                    segs.append((top.end, stack[-1].name if stack else None))
                segs.append((s.start, s.name))
                stack.append(s)
            while stack:
                top = stack.pop()
                segs.append((top.end, stack[-1].name if stack else None))
            self._seg_t = [t0 for t0, _ in segs]
            self._seg_name = [name for _, name in segs]
        i = bisect.bisect_right(self._seg_t, t) - 1
        name = self._seg_name[i] if i >= 0 else None
        return name or "no host span"


def is_async(name: str) -> bool:
    """An async start or done op: a transfer in flight, not core work."""
    m = _OPCODE.match(name)
    return bool(m) and m.group(1).endswith(("-start", "-done"))


def _self_times(ops: List[Op]) -> None:
    """Sort ops and give each its time not covered by ops nested in it."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(ops: List[Op], window: Interval) -> List[Op]:
    lo, hi = window
    return [o for o in ops if o.end > lo and o.start < hi]


class Summary:
    """The device side of one traced window, for the metric readers."""

    def __init__(self, trace: Trace, window: Interval):
        self.trace, self.window = trace, window
        lo, hi = window
        self.window_s = (hi - lo) * 1e-9
        self.per_device = {}
        for plane, dev in trace.devices.items():
            ops = clip(dev["ops"], window)
            merged = union([(max(o.start, lo), min(o.end, hi)) for o in ops])
            busy = sum(e - s for s, e in merged)
            gaps, prev = [], lo
            for s, e in merged:
                if s > prev:
                    gaps.append((prev, s))
                prev = e
            if hi > prev:
                gaps.append((prev, hi))
            self.per_device[plane] = {
                "ops": ops, "modules": clip(dev["modules"], window),
                "busy_ns": busy, "gaps": gaps}

    @property
    def has_device(self) -> bool:
        return any(d["ops"] for d in self.per_device.values())

    @property
    def busy_s(self) -> float:
        n = max(1, len(self.per_device))
        return sum(d["busy_ns"] for d in self.per_device.values()) * 1e-9 / n

    def modules(self, fn: str) -> List[Op]:
        """Runs of the program step ``jit_<fn>`` in the window, all chips."""
        pre = f"jit_{fn}("
        return [m for d in self.per_device.values() for m in d["modules"]
                if m.name.startswith(pre)]

    @staticmethod
    def _steps_of(d, ops: List[Op]) -> List[str]:
        """The step (``<fn>`` of the ``jit_<fn>(...)`` module run holding
        each op's start), or "" outside every run."""
        mods = sorted((m.start, m.end, m.name) for m in d["modules"])
        starts = [s for s, _, _ in mods]
        out = []
        for o in ops:
            i = bisect.bisect_right(starts, o.start) - 1
            inside = i >= 0 and o.start < mods[i][1]
            out.append(mods[i][2].split("(", 1)[0][4:] if inside else "")
        return out

    def op_seconds(self, pred: Callable[[Op], bool],
                   within: Optional[str] = None) -> float:
        """Self time of the ops matching ``pred`` (inside runs of step
        ``within`` only, when given), summed over chips."""
        total = 0
        for d in self.per_device.values():
            ops = [o for o in d["ops"] if pred(o)]
            if within is not None:
                ops = [o for o, fn in zip(ops, self._steps_of(d, ops))
                       if fn == within]
            total += sum(o.self_ns for o in ops)
        return total * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """Self time by instruction and step, largest first (averaged over
        chips): ``[["decode_step %fusion.3 bf16[64,960] fusion", s], ...]``."""
        agg: Dict[str, int] = collections.Counter()
        for d in self.per_device.values():
            for o, fn in zip(d["ops"], self._steps_of(d, d["ops"])):
                agg[f"{fn or 'no step'} {o.label}"] += o.self_ns
        k = max(1, len(self.per_device))
        return [[name, ns * 1e-9 / k] for name, ns in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[List]:
        """Idle seconds of the window grouped by the host span open in the
        middle of each gap, largest first (averaged over chips)."""
        agg: Dict[str, int] = collections.Counter()
        for d in self.per_device.values():
            for s, e in d["gaps"]:
                agg[self.trace.span_at((s + e) // 2)] += e - s
        k = max(1, len(self.per_device))
        return [[name, ns * 1e-9 / k] for name, ns in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def is_sac(op: Op) -> bool:
    return SAC_MARK in op.name


def reduce(log_dir: str, window_span: str) -> Optional[Summary]:
    """Read the capture in ``log_dir`` over the host span ``window_span``;
    None when the trace holds no device plane."""
    path = xplane_path(log_dir)
    if path is None:
        return None
    trace = Trace.from_file(path, window_span)
    bounds = trace.span_bounds(window_span)
    if bounds is None or not trace.devices:
        return None
    return Summary(trace, bounds)
