"""The program's own host spans in a traced stretch, for the metric readers.

The serving engines open ``serve.*`` spans (``repro/runtime/spans.py``) on
the thread that steps them, which is the thread that opens the harness's
spans, so ``Summary.trace.spans`` holds them beside ``scheduler_step`` and
JAX's own ``PjitFunction(...)`` / ``np.asarray(...)``.  A span whose name
ends in ``_sync`` is the program waiting on the device; every other
``serve.*`` span is host work.  Program spans nest strictly.

Match rules (PERF.md repeats them):

* an idle stretch of the device belongs to the innermost ``serve.*`` span
  open over it, split exactly at span edges (not by the gap's midpoint,
  as ``Summary.idle_by_span`` does); idle time under no ``serve.*`` span is
  the harness's own (arrival waits, its ``submit``) and is left out;
* a span belongs to the stretch when it starts inside it;
* a span's counters are its event stats in the capture file.
  ``trace.Trace`` keeps names and times only, so :func:`stats` reads them
  again from the run's capture: the ``.xplane.pb`` under the run's scratch
  directories (``chipbench_trace_*`` in the temporary directory, kept until
  the readers are done) whose host line holds a span spanning exactly the
  stretch.  Finding none is a fault of the harness, and raises.

A program without ``serve.*`` spans gives every reader nothing to read.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import tempfile
from typing import Dict, List, Optional, Tuple

SERVE = "serve."
SYNC = "_sync"

Segments = Tuple[List[int], List[Optional[str]]]


def serve_spans(summary) -> List:
    """The ``serve.*`` spans that start inside the traced stretch."""
    lo, hi = summary.window
    return [s for s in summary.trace.spans
            if s.name.startswith(SERVE) and lo <= s.start < hi]


def segments(spans) -> Segments:
    """``(bounds, labels)``: from ``bounds[i]`` up to ``bounds[i + 1]`` the
    innermost open ``serve.*`` span is ``labels[i]`` (None: none)."""
    bounds: List[int] = []
    labels: List[Optional[str]] = []
    stack: List = []

    def mark(t: int) -> None:
        bounds.append(t)
        labels.append(stack[-1].name if stack else None)

    for s in sorted((s for s in spans if s.name.startswith(SERVE)),
                    key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            mark(stack.pop().end)
        stack.append(s)
        mark(s.start)
    while stack:
        mark(stack.pop().end)
    return bounds, labels


def innermost_serve(segs: Segments, t: int) -> Optional[str]:
    """The innermost ``serve.*`` span open at ``t``, or None."""
    bounds, labels = segs
    i = bisect.bisect_right(bounds, t) - 1
    return labels[i] if i >= 0 else None


def idle_by_serve(summary) -> Dict[str, float]:
    """Idle seconds of the stretch by innermost ``serve.*`` span name
    (averaged over chips); empty when the stretch holds no such span."""
    if not serve_spans(summary):
        return {}
    bounds, labels = segments(summary.trace.spans)
    agg: Dict[str, int] = collections.Counter()
    for d in summary.per_device.values():
        for s, e in d["gaps"]:
            i = bisect.bisect_right(bounds, s) - 1
            t = s
            while t < e:
                cut = min(e, bounds[i + 1]) if i + 1 < len(bounds) else e
                if i >= 0 and labels[i] is not None and cut > t:
                    agg[labels[i]] += cut - t
                t, i = cut, i + 1
    k = max(1, len(summary.per_device))
    return {name: ns * 1e-9 / k for name, ns in agg.items()}


def idle_share(summary, sync: bool) -> Optional[float]:
    """% of the stretch the device sat idle under a ``*_sync`` span
    (``sync``) or under any other ``serve.*`` span; None without them."""
    if summary is None or not summary.has_device:
        return None
    by_span = idle_by_serve(summary)
    if not by_span:
        return None
    idle = sum(v for name, v in by_span.items()
               if name.endswith(SYNC) == sync)
    return 100.0 * idle / summary.window_s


# ------------------------------------------------------------ counters

def stats(summary) -> Dict[Tuple[str, int], Dict]:
    """Counters of the ``serve.*`` spans, keyed by (name, start ns); read
    once from the run's capture and kept on the summary (``span_stats``).
    Raises when no capture holds the stretch: call it only for a stretch
    that holds ``serve.*`` spans."""
    if getattr(summary, "span_stats", None) is None:
        summary.span_stats = _read_capture(summary.window)
    return summary.span_stats


def _read_capture(window) -> Dict[Tuple[str, int], Dict]:
    pattern = os.path.join(tempfile.gettempdir(), "chipbench_trace_*", "**",
                           "*.xplane.pb")
    paths = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime,
                   reverse=True)
    for path in paths:
        found = read_stats(path, window)
        if found is not None:
            return found
    raise RuntimeError(
        f"the stretch holds serve.* spans, but none of the {len(paths)} "
        f"captures matching {pattern} holds a span over it")


def read_stats(path: str, window) -> Optional[Dict[Tuple[str, int], Dict]]:
    """Counters of the ``serve.*`` spans on the host line of ``path`` that
    holds a span from ``window[0]`` to ``window[1]``; None when no line
    does."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if any((int(e.start_ns), int(e.start_ns + e.duration_ns))
                   == tuple(window) for e in events):
                return {(e.name, int(e.start_ns)): dict(e.stats)
                        for e in events if e.name.startswith(SERVE)}
    return None
