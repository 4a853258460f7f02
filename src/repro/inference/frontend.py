"""Shared request front-end plumbing for the serving engines.

``CNNServingEngine`` (images) and ``ServingEngine`` (LM prompts) expose the
same request surface; what differs is the payload and how requests execute.
This module owns the parts that must never diverge between them:

* :class:`Request` — one submitted unit of work and its lifecycle state
  machine (``queued -> running -> done`` with ``cancelled``/``expired``
  exits; docs/DESIGN.md §9).
* :class:`RequestHandle` — what ``submit()`` returns.  It subclasses
  ``int`` so every pre-handle call site keeps working (the handle *is*
  the request id: sortable, hashable, ``==`` against plain ints, usable
  as the ``drain()`` dict key), while the redesigned API rides along:
  ``result()`` blocks until this request finishes, ``stream()`` yields
  tokens as they are generated, ``cancel()`` withdraws the request, and
  ``priority``/``deadline`` expose the admission fields.
* :class:`RequestFrontEnd` — bucket validation, id/pending bookkeeping,
  the virtual-launch clock (``ticks``), the sliding per-request log, and
  the latency summary with its queue-wait vs decode-time breakdown.

Each engine keeps its own ``submit``/``drain`` (payload checks and
execution are engine-specific) and records served requests through
:meth:`RequestFrontEnd._log_request`.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import (Any, Deque, Dict, Iterator, List, Optional,
                    Sequence)

import numpy as np

# Request lifecycle states (docs/DESIGN.md §9 state machine; FAILED added
# by the resilience layer, docs/DESIGN.md §10)
QUEUED = "queued"        # submitted, waiting for admission
RUNNING = "running"      # admitted to a slot (continuous) / being drained
DONE = "done"            # all tokens produced
CANCELLED = "cancelled"  # withdrawn by cancel()
EXPIRED = "expired"      # deadline passed before admission
FAILED = "failed"        # terminal: retries exhausted (fault policy)

TERMINAL = (DONE, CANCELLED, EXPIRED, FAILED)


class DeadlineExceeded(RuntimeError):
    """result() on a request whose deadline lapsed before admission."""


class RequestFailed(RuntimeError):
    """result() on a request that exhausted its fault-policy retries."""


def validate_buckets(buckets: Sequence[int]) -> None:
    """Padding buckets must be non-empty, positive and ascending (drain
    and the admission batcher pad a chunk up to the smallest bucket that
    fits, so order is load-bearing)."""
    if not buckets:
        raise ValueError("buckets must be a non-empty ascending tuple")
    if tuple(buckets) != tuple(sorted(buckets)) or \
            not all(b > 0 for b in buckets):
        raise ValueError(f"buckets must be positive ascending, "
                         f"got {tuple(buckets)}")


@dataclasses.dataclass
class Request:
    """One submitted request and its lifecycle bookkeeping.

    ``payload`` is engine-specific (a 1-D token prompt for the LM engine,
    an [H, W, C] image for the CNN engine).  Wall-clock stamps
    (``submit_t``/``admit_t``/``first_token_t``/``finish_t``; the first
    token's only under the continuous scheduler, once that token is on the
    host) feed ``latency_stats``;
    the ``*_tick`` twins are stamped from the engine's deterministic
    virtual-launch clock so benches can compare schedulers bit-for-bit.
    """

    id: int
    payload: Any
    num_tokens: int = 0
    priority: int = 0
    deadline: Optional[float] = None      # seconds from submit; None = never
    state: str = QUEUED
    out: List[int] = dataclasses.field(default_factory=list)
    result: Optional[np.ndarray] = None
    slot: Optional[int] = None
    submit_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    submit_tick: int = 0
    admit_tick: int = 0
    finish_tick: int = 0
    # resilience bookkeeping (docs/DESIGN.md §10): recovery attempts so
    # far, the wall-clock instant before which admission must not retry
    # (exponential-backoff window), and the terminal failure reason.
    retries: int = 0
    retry_at: float = 0.0
    error: Optional[str] = None

    @property
    def prompt_len(self) -> int:
        return int(getattr(self.payload, "shape", (0,))[0])

    def expired(self, now: float) -> bool:
        return (self.state == QUEUED and self.deadline is not None
                and now - self.submit_t > self.deadline)


class RequestHandle(int):
    """``submit()``'s return value: the request id, plus the request API.

    Subclasses ``int`` so code written against the old id-returning
    ``submit()`` — ``sorted(handles)``, ``results[handle]``,
    ``handle == 3`` — is untouched, while new call sites get
    ``result()/stream()/cancel()`` and the admission fields.
    """

    _req: Request
    _engine: "RequestFrontEnd"

    def __new__(cls, req: Request, engine: "RequestFrontEnd"):
        h = super().__new__(cls, req.id)
        h._req = req
        h._engine = engine
        return h

    @property
    def state(self) -> str:
        return self._req.state

    @property
    def priority(self) -> int:
        return self._req.priority

    @property
    def deadline(self) -> Optional[float]:
        return self._req.deadline

    @property
    def submit_t(self) -> float:
        """``time.perf_counter()`` at submit."""
        return self._req.submit_t

    @property
    def admit_t(self) -> float:
        """``time.perf_counter()`` at admission (0.0 while queued)."""
        return self._req.admit_t

    @property
    def first_token_t(self) -> float:
        """``time.perf_counter()`` once the first token reached the host
        (continuous scheduler; 0.0 before that, and on other paths)."""
        return self._req.first_token_t

    @property
    def finish_t(self) -> float:
        """``time.perf_counter()`` at completion (0.0 until then)."""
        return self._req.finish_t

    @property
    def retries(self) -> int:
        """Recovery attempts consumed so far (fault policy; §10)."""
        return self._req.retries

    @property
    def error(self) -> Optional[str]:
        """Terminal failure reason once the request is FAILED."""
        return self._req.error

    def tokens_so_far(self) -> np.ndarray:
        """Tokens generated so far (without blocking)."""
        return np.asarray(self._req.out, dtype=np.int32)

    def result(self) -> np.ndarray:
        """Block until this request finishes; returns its output tokens
        (LM) or logits (CNN).  Raises on cancel/deadline expiry."""
        return self._engine._result(self._req)

    def stream(self) -> Iterator[int]:
        """Yield output tokens as they are generated.  Under the
        continuous scheduler tokens arrive per decode step; under the
        batch scheduler the request is drained first and then replayed
        token-by-token (degenerate streaming, same contract)."""
        return self._engine._stream(self._req)

    def cancel(self) -> bool:
        """Withdraw the request.  True if it was still cancellable
        (queued, or mid-decode under the continuous scheduler — its KV
        blocks are freed immediately); False once done."""
        return self._engine._cancel(self._req)


class RequestFrontEnd:
    """Mixin: request bookkeeping + latency accounting for the engines."""

    _next_id: int
    _pending: List[Request]
    _requests: Dict[int, Request]
    _request_log: Deque[Dict[str, Any]]
    ticks: int

    def _init_front_end(self, stats_window: int) -> None:
        self._next_id = 0
        self._pending = []
        self._requests = {}
        self._request_log = collections.deque(maxlen=stats_window)
        # Virtual-launch clock: +1 per jitted prefill/decode/forward
        # launch.  Deterministic (unlike wall time), so scheduler benches
        # gate latency-in-ticks in CI (bench_kernels serving_load_sweep).
        self.ticks = 0
        # Resilience telemetry (docs/DESIGN.md §10): monotonic counters
        # (retries, failed_requests, nan_quarantined, recoveries,
        # watchdog_timeouts, straggler_steps, degradations, ...) merged
        # into latency_stats(), plus a bounded event log of the notable
        # transitions (recoveries, impl demotions, integrity repairs).
        self._fault_counters: collections.Counter = collections.Counter()
        self._fault_events: Deque[Dict[str, Any]] = collections.deque(
            maxlen=stats_window)
        # MoE routing-load accounting (docs/DESIGN.md §13): the counters
        # are process-wide (they accumulate from inside jitted steps via
        # debug callback), so each engine snapshots at construction and
        # latency_stats() reports its own delta.
        from repro.core import routing_stats
        self._routing_stats_base = routing_stats.routing_stats()

    def _fault_event(self, kind: str, **detail: Any) -> None:
        self._fault_counters[kind] += 1
        self._fault_events.append({"kind": kind, "tick": self.ticks,
                                   **detail})

    def fault_events(self) -> List[Dict[str, Any]]:
        """Notable resilience transitions (bounded sliding window)."""
        return list(self._fault_events)

    def _new_request(self, payload: Any, num_tokens: int = 0, *,
                     priority: int = 0,
                     deadline: Optional[float] = None) -> RequestHandle:
        req = Request(id=self._next_id, payload=payload,
                      num_tokens=num_tokens, priority=priority,
                      deadline=deadline, submit_t=time.perf_counter(),
                      submit_tick=self.ticks)
        self._next_id += 1
        self._requests[req.id] = req
        self._pending.append(req)
        return RequestHandle(req, self)

    def _log_request(self, **entry: Any) -> None:
        self._request_log.append(entry)

    # ---- handle backends: batch-path defaults (drain serves everything).
    # ServingEngine overrides these when the continuous scheduler is on.

    def _finished_result(self, req: Request) -> np.ndarray:
        if req.state == CANCELLED:
            raise RuntimeError(f"request {req.id} was cancelled")
        if req.state == EXPIRED:
            raise DeadlineExceeded(
                f"request {req.id} missed its deadline "
                f"({req.deadline:.3f}s) before admission")
        if req.state == FAILED:
            raise RequestFailed(
                f"request {req.id} failed after {req.retries} retries: "
                f"{req.error}")
        assert req.state == DONE, req
        return req.result

    def _result(self, req: Request) -> np.ndarray:
        if req.state in (QUEUED, RUNNING):
            self.drain()
        return self._finished_result(req)

    def _stream(self, req: Request) -> Iterator[int]:
        out = self._result(req)
        yield from (int(t) for t in np.asarray(out).reshape(-1))

    def _cancel(self, req: Request) -> bool:
        if req.state != QUEUED:
            return False
        req.state = CANCELLED
        self._pending = [r for r in self._pending if r.id != req.id]
        return True

    # ------------------------------------------------------------- stats

    def latency_stats(self) -> Dict[str, float]:
        """Per-request latency distribution over the last ``stats_window``
        served requests (a sliding window, bounded by construction).

        Beyond total latency, the summary breaks out **queue wait**
        (submit -> start of execution) vs **decode time** (execution
        start -> completion) at p50/p95 each, and **time to first token**
        (submit -> first token on the host) where the continuous scheduler
        stamped it, so the batch and continuous schedulers are comparable
        from the CLI: batch mode hides its wave barrier in queue wait,
        continuous in slightly longer decode (shared slots).
        """
        lat = np.array([r["latency_ms"] for r in self._request_log])
        if lat.size == 0:
            return {"requests": 0,
                    **{k: int(v) for k, v in self._fault_counters.items()
                       if v},
                    **self._routing_stats_delta()}
        out = {
            "requests": int(lat.size),
            "mean_ms": float(lat.mean()),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "max_ms": float(lat.max()),
        }
        fill = [r["batch_fill"] for r in self._request_log
                if "batch_fill" in r]
        if fill:
            out["mean_batch_fill"] = float(np.mean(fill))
        for key, label in (("queue_wait_ms", "queue_wait"),
                           ("decode_ms", "decode"), ("ttft_ms", "ttft")):
            vals = np.array([r[key] for r in self._request_log if key in r])
            if vals.size:
                out[f"{label}_p50_ms"] = float(np.percentile(vals, 50))
                out[f"{label}_p95_ms"] = float(np.percentile(vals, 95))
        # resilience counters (docs/DESIGN.md §10): zero-valued keys are
        # omitted — a fault-free engine's stats look exactly as before
        out.update({k: int(v) for k, v in self._fault_counters.items() if v})
        # MoE routing load (docs/DESIGN.md §13): present only when routed
        # MoE layers actually ran under this engine
        out.update(self._routing_stats_delta())
        return out

    def _routing_stats_delta(self) -> Dict[str, int]:
        """This engine's MoE routing load since construction: per-step
        routed (token, expert) assignment counts and capacity-overflow
        drops — empty when no MoE layer ran, so stats dicts are unchanged
        for dense engines."""
        from repro.core import routing_stats
        cur = routing_stats.routing_stats()
        steps = cur["routing_steps"] - self._routing_stats_base["routing_steps"]
        if steps <= 0:
            return {}
        return {"routed_tokens": int(cur["routed_tokens"]
                                     - self._routing_stats_base["routed_tokens"]),
                "capacity_dropped": int(
                    cur["capacity_dropped"]
                    - self._routing_stats_base["capacity_dropped"]),
                "routing_steps": int(steps)}

