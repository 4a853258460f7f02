"""Serving-side resilience: fault policy, injection, and weight integrity.

The training loop has had bounded-retry restarts and straggler detection
since the seed (``runtime/fault_tolerance.py``); the serving stack had
none — an exception in one jitted decode step killed every in-flight
request, one NaN logit poisoned its whole micro-batch, and nothing
integrity-checked the kneaded planes or schedule arrays whose corruption
silently changes *which work items execute* (the flip side of the kneaded
form being an exact re-encoding).  This module is the serving half of the
fault story (docs/DESIGN.md §10):

* :class:`ServingFaultPolicy` — the knob set carried on ``ServingConfig``:
  bounded per-request retries with capped exponential backoff, the
  per-decode-step watchdog (timeout + straggler watermark, built on
  :class:`~repro.runtime.fault_tolerance.StepTimer`), the NaN/Inf logit
  quarantine guard, and the graceful-degradation ladder that demotes the
  engine impl ``pallas -> planes -> float`` after repeated kernel faults.
* :class:`EngineFaultInjector` — deterministic chaos hooks for tests and
  the ``serving_fault_sweep`` bench, extending the training-loop
  :class:`~repro.runtime.fault_tolerance.FailureInjector` idea to the
  engine's step loop: injected step exceptions, per-request NaN logits,
  and simulated slot (device-row) loss, all keyed on step/request ids so
  every chaos run replays identically.
* Weight corruption + verification helpers — flip bits in a kneaded
  weight's planes/presence/schedule arrays (for chaos tests), and
  :func:`verify_kneaded_tree` to sweep a serving param tree against its
  knead-time checksums, repairing corrupt leaves by re-kneading from the
  float checkpoint (:func:`~repro.core.kneading.reknead_like`).

Recovery is **bit-exact by replay**: greedy decode is deterministic and
per-row independent, so a request re-admitted after a fault — re-prefilled
on its original prompt and re-decoded step by step — regenerates exactly
the tokens it had already produced and continues identically to a
fault-free run.  (Recovery deliberately does NOT re-prefill
``prompt + generated-prefix`` as one longer sequence: changing a matmul's
M extent changes the f32 reduction order on real backends, which would
break the bitwise guarantee the schedulers are tested against.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.kneading import (KneadedWeight, ShardedKneadedWeight,
                                 reknead_like)
from repro.runtime import fault_tolerance as ft

PyTree = Any

__all__ = [
    "EngineFaultInjector",
    "InjectedKernelFault",
    "ServingFaultPolicy",
    "StepTimeout",
    "corrupt_array_word",
    "corrupt_kneaded",
    "verify_kneaded_tree",
]


class InjectedKernelFault(ft.InjectedFailure):
    """Deterministically injected engine/kernel step failure."""


class StepTimeout(RuntimeError):
    """A watchdogged decode step exceeded ``step_timeout_s``."""


@dataclasses.dataclass
class EngineFaultInjector:
    """Deterministic fault plan for the serving engine's step loop.

    All hooks key on the scheduler's step counter or on request ids, so a
    chaos run is exactly reproducible.  ``fail_once`` mirrors the training
    injector: each step-indexed fault fires once (the recovery path then
    gets a clean retry); NaN poisoning keys on request id and fires on
    *every* launch that request participates in (modelling persistent bad
    state — the request must exhaust its retries and FAIL), unless
    ``nan_once`` is set (transient glitch — the retry succeeds).
    """

    # indices into the scheduler's decode/prefill launch-ATTEMPT counters
    # (failed attempts advance them too, so consecutive indices model a
    # fault streak and a lone index a transient glitch)
    fail_decode_steps: Tuple[int, ...] = ()
    fail_prefill_steps: Tuple[int, ...] = ()
    nan_request_ids: Tuple[int, ...] = ()
    nan_once: bool = False
    # simulated loss of one slot's device state: (step, slot) pairs
    lose_slot_steps: Tuple[Tuple[int, int], ...] = ()
    fail_once: bool = True

    def __post_init__(self):
        self._decode = ft.FailureInjector(self.fail_decode_steps,
                                          fail_once=self.fail_once)
        self._prefill = ft.FailureInjector(self.fail_prefill_steps,
                                           fail_once=self.fail_once)
        self._nan_pending = set(self.nan_request_ids)
        self._loss_pending = set(self.lose_slot_steps)

    def maybe_fail_decode(self, step: int) -> None:
        try:
            self._decode.maybe_fail(step)
        except ft.InjectedFailure as exc:
            raise InjectedKernelFault(
                f"injected kernel fault at decode step {step}") from exc

    def maybe_fail_prefill(self, step: int) -> None:
        try:
            self._prefill.maybe_fail(step)
        except ft.InjectedFailure as exc:
            raise InjectedKernelFault(
                f"injected kernel fault at prefill step {step}") from exc

    def poison_request(self, request_id: int) -> bool:
        """Should this request's logits row be NaN-poisoned this launch?"""
        if request_id not in self._nan_pending:
            return False
        if self.nan_once:
            self._nan_pending.discard(request_id)
        return True

    def lost_slots(self, step: int) -> List[int]:
        """Slots whose device state is 'lost' at this step (fires once)."""
        hits = [s for (t, s) in self._loss_pending if t == step]
        for s in hits:
            self._loss_pending.discard((step, s))
        return hits


@dataclasses.dataclass
class ServingFaultPolicy:
    """Fault handling for the serving engines (docs/DESIGN.md §10).

    Attached to ``ServingConfig(fault_policy=...)``.  ``None`` (the
    default) keeps the pre-resilience behavior exactly: no guards, no
    recovery, exceptions propagate.

    Attributes:
      max_retries:      recovery attempts per request before the terminal
                        ``FAILED`` state (counts NaN quarantines, slot
                        losses, and engine-step failures alike).
      retry_backoff_s / backoff_mult / backoff_cap_s: per-request
                        exponential backoff window between retries —
                        admission skips a request until its window passes.
      step_timeout_s:   watchdog threshold on one decode launch (0 = off).
                        A jitted step cannot be preempted mid-flight, so
                        the watchdog detects *after* the launch returns:
                        it counts ``watchdog_timeouts``, and with
                        ``timeout_is_fault`` treats the step as failed
                        (the recovery path re-admits in-flight work).
      straggler_k:      :class:`~repro.runtime.fault_tolerance.StepTimer`
                        watermark — steps beyond median + k*MAD count as
                        ``straggler_steps`` in ``latency_stats()``.
      nan_guard:        check prefill/decode logits rows for NaN/Inf and
                        quarantine ONLY the offending request (requeue or
                        FAIL), never the batch.  Costs one host fetch of
                        the logits per launch — leave on; disable only for
                        benchmarking the guard itself.
      demote_after:     consecutive engine-step faults before the impl
                        demotes one rung down ``fallback_impls``
                        (pallas -> planes stays bit-exact; planes ->
                        float trades exactness for availability and is
                        logged as a degradation event).
      fallback_impls:   the degradation ladder, strongest-first.
      verify_weights:   verify kneaded-weight checksums at engine init
                        (restored/transported params; corrupt leaves are
                        re-kneaded from the float checkpoint, which the
                        engine still holds at init time).
      injector:         deterministic chaos hooks (tests/bench only).
    """

    max_retries: int = 2
    retry_backoff_s: float = 0.02
    backoff_mult: float = 2.0
    backoff_cap_s: float = 1.0
    step_timeout_s: float = 0.0
    timeout_is_fault: bool = False
    straggler_k: float = 5.0
    nan_guard: bool = True
    demote_after: int = 2
    fallback_impls: Tuple[str, ...] = ("planes", "float")
    verify_weights: bool = False
    injector: Optional[EngineFaultInjector] = None

    def backoff_for(self, retries: int) -> float:
        """Backoff window before retry number ``retries`` (1-based)."""
        raw = self.retry_backoff_s * (self.backoff_mult ** max(0,
                                                               retries - 1))
        return min(raw, self.backoff_cap_s)


# ---------------------------------------------------------------- corruption


def corrupt_array_word(x, flat_index: int = 0, xor: int = 1):
    """Return a copy of ``x`` with one word XOR-flipped (chaos helper)."""
    arr = np.asarray(x).copy()
    flat = arr.reshape(-1)
    if np.issubdtype(arr.dtype, np.integer):
        flat[flat_index] ^= xor
    else:
        flat[flat_index] = flat[flat_index] + 1.0
    return jnp.asarray(arr)


_CORRUPTIBLE = {
    "planes": "planes",
    "signs": "signs",
    "occupancy": "occupancy",
    "schedule.counts": "counts",
    "schedule.ktile_ids": "ktile_ids",
    "schedule.plane_bits": "plane_bits",
}


def corrupt_kneaded(kw: KneadedWeight, field: str = "occupancy",
                    flat_index: int = 0, xor: int = 1) -> KneadedWeight:
    """Flip one word of a kneaded weight's array ``field`` (dotted names
    reach into the schedule).  The result fails ``verify()`` on exactly
    that field — checksums are deliberately NOT re-stamped."""
    if field not in _CORRUPTIBLE:
        raise ValueError(f"field must be one of {sorted(_CORRUPTIBLE)}, "
                         f"got {field!r}")
    if field.startswith("schedule."):
        leaf = field.split(".", 1)[1]
        sched = kw.schedule
        new_sched = dataclasses.replace(
            sched, **{leaf: corrupt_array_word(getattr(sched, leaf),
                                               flat_index, xor)})
        return dataclasses.replace(kw, schedule=new_sched)
    return dataclasses.replace(
        kw, **{field: corrupt_array_word(getattr(kw, field),
                                         flat_index, xor)})


# ----------------------------------------------------------- tree integrity


def verify_kneaded_tree(params: PyTree, float_params: Optional[PyTree] = None,
                        *, shards: int = 0, repair: bool = True,
                        ) -> Tuple[PyTree, List[Dict[str, Any]]]:
    """Sweep a serving param tree for corrupted kneaded leaves.

    Every :class:`KneadedWeight` / :class:`ShardedKneadedWeight` leaf is
    verified against its knead-time checksums.  With ``repair`` and a
    ``float_params`` tree of the same structure (the engine's pre-knead
    checkpoint), corrupt leaves are rebuilt in place via
    :func:`~repro.core.kneading.reknead_like` — deterministic, so the
    repaired leaf is bit-identical to the never-corrupted one.

    Returns ``(maybe-repaired tree, report)`` where each report row is
    ``{"path", "fields", "repaired"}`` for one corrupt leaf (empty report
    = tree intact).  Raises
    :class:`~repro.core.schedule.KneadedIntegrityError` when a corrupt
    leaf cannot be repaired (no float source).
    """
    import jax

    from repro.core.schedule import KneadedIntegrityError

    kinds = (KneadedWeight, ShardedKneadedWeight)
    is_kw = lambda x: isinstance(x, kinds)            # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(params,
                                                         is_leaf=is_kw)
    floats = {}
    if float_params is not None:
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                float_params)[0]:
            floats[jax.tree_util.keystr(path)] = leaf
    report: List[Dict[str, Any]] = []
    out = []
    for path, leaf in flat:
        if not is_kw(leaf):
            out.append(leaf)
            continue
        bad = leaf.verify()
        if not bad:
            out.append(leaf)
            continue
        key = jax.tree_util.keystr(path)
        src = floats.get(key)
        if repair and src is not None:
            leaf = reknead_like(leaf, src, shards=shards)
            report.append({"path": key, "fields": bad, "repaired": True})
        else:
            report.append({"path": key, "fields": bad, "repaired": False})
        out.append(leaf)
    unrepaired = [r for r in report if not r["repaired"]]
    if unrepaired:
        raise KneadedIntegrityError(
            "corrupt kneaded weights with no float source to re-knead "
            f"from: {[(r['path'], r['fields']) for r in unrepaired]}")
    return jax.tree_util.tree_unflatten(treedef, out), report
