"""Continuous-batching decode scheduler for the LM serving engine.

The batch-synchronous ``drain()`` path serves requests in waves: a
micro-batch prefills together, decodes together for the chunk-max token
budget, and nothing new is admitted until the wave retires.  Under load
that wave barrier is exactly the ineffectual work Tetris compacts out of
the MXU: decode steps spent on rows that are finished, padded, or not
yet admitted.  :class:`ContinuousScheduler` removes the barrier at step
granularity (docs/DESIGN.md §9):

* **Slot table** — a fixed capacity of ``max_inflight`` in-flight rows.
  Each scheduler step admits queued prompts into free slots (one padded
  prefill launch, interleaved with decode), runs ONE decode launch for
  every live slot, appends each live request's next token, and retires
  finished requests immediately — their slots and KV blocks free the
  same step, so the next admission can reuse them.
* **KV block pool** (:class:`~repro.inference.kv_pool.KVBlockPool`) —
  admission reserves ``prompt + budget`` tokens of block-granular KV up
  front; the jitted decode step is shaped to the pool's high-water
  extent (largest live reservation, rounded to a block) instead of
  ``max_len``, so short-request traffic stops paying long-request
  attention costs.
* **Compile-cache buckets** — the padding-bucket machinery of the batch
  path becomes the compile-cache layer underneath: the decode batch dim
  pads to the smallest slot-capacity bucket covering the highest live
  slot, and prefill pads to the smallest bucket covering the admission
  group, so jit sees one decode shape per (slot bucket, block extent)
  and one prefill shape per (bucket, prompt length).

Bit-exactness: every per-row computation (masked cache writes, per-row
positions, attention masked to ``<= pos``) is row-independent, and
greedy selection is invariant to the batch rows around it and to the
padded cache extent beyond the mask — so a request's generation here is
bit-identical to the batch path's ``generate()`` (regression-tested for
the planes and pallas impls in tests/test_scheduler.py).

Fault handling (docs/DESIGN.md §10; off unless the engine carries a
:class:`~repro.inference.resilience.ServingFaultPolicy`): the step loop
wraps in a recovery path — an engine-step exception requeues every
in-flight request (bounded per-request retries with backoff, then the
terminal ``FAILED`` state), rebuilds the slot table and KV pool from
scratch, and replays survivors from their prompt.  Replay is bit-exact
for greedy decode: the same row-independence that makes the scheduler
match ``generate()`` makes a re-admitted request regenerate exactly the
prefix it had already produced.  A NaN/Inf logit guard quarantines only
the offending request's row; a :class:`~repro.runtime.fault_tolerance
.StepTimer` watchdog flags slow/stuck decode launches; repeated step
faults demote the engine impl down its fallback ladder.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.inference import frontend as fe
from repro.inference.kv_pool import KVBlockPool
from repro.inference.resilience import StepTimeout
from repro.runtime import fault_tolerance as ft
from repro.runtime.spans import span

PyTree = Any

# Cache keys with a sequence axis and their pad values (mirrors
# ServingEngine._pad_cache: KV stores zero-pad, int8-KV scales pad 1.0
# so dequantization of masked lanes stays finite).  Name-keyed on the
# model families' cache dicts — never shape-sniffed (the zamba2 hybrid
# lesson, see _pad_cache's docstring).
_SEQ_PAD = {"k": 0.0, "v": 0.0, "k_scale": 1.0, "v_scale": 1.0}


class ContinuousScheduler:
    """Step-level slot scheduler over a ServingEngine's jitted model fns.

    The engine owns params, jitted prefill/decode, token selection and
    the request front end; the scheduler owns the slot table, the KV
    pool, and the per-step admit -> decode -> retire loop.
    """

    def __init__(self, engine) -> None:
        self.eng = engine
        scfg = engine.scfg
        self.capacity = scfg.max_inflight
        self.pool = KVBlockPool(scfg.max_inflight, scfg.max_len,
                                block=scfg.kv_block,
                                total_tokens=scfg.kv_pool_tokens)
        # slot -> running Request (fixed table; None = free)
        self.slots: List[Optional[fe.Request]] = [None] * self.capacity
        # batch-dim compile-cache buckets, clipped to the slot capacity
        bks = [b for b in scfg.buckets if b < self.capacity]
        self.slot_buckets: Tuple[int, ...] = tuple(bks) + (self.capacity,)
        self._cache: Optional[PyTree] = None
        self._batch = 0            # current cache batch dim (a slot bucket)
        self._extent = 0           # current cache seq extent (block multiple)
        self._axes: Dict[str, Tuple[int, Optional[int]]] = \
            self._detect_axes(engine.model)
        self._key = jax.random.PRNGKey(0)
        # resilience (docs/DESIGN.md §10): None = pre-resilience behavior
        self.policy = getattr(scfg, "fault_policy", None)
        self._timer = (ft.StepTimer(k=self.policy.straggler_k)
                       if self.policy is not None else None)
        self._step_idx = 0         # scheduler steps (slot-loss injection key)
        # launch ATTEMPTS, counted before the launch so a failed one still
        # advances — a one-shot injected fault index then hits exactly once
        self._decode_calls = 0     # decode attempts (injection/watchdog key)
        self._prefill_calls = 0    # prefill attempts (injection key)
        self._fault_streak = 0     # consecutive failed steps (demotion gate)

    # ----------------------------------------------------- cache geometry

    @staticmethod
    def _detect_axes(model) -> Dict[str, Tuple[int, Optional[int]]]:
        """Per-cache-leaf (batch_axis, seq_axis) from cache_spec diffs.

        Axes are found by varying one spec argument at a time and
        diffing shapes — robust across families (stacked [L, B, ...]
        leaves, SSM states with no seq axis at all) without hardcoding
        layouts beyond what the model itself reports.
        """
        b1 = model.cache_spec(batch=1, max_len=16)
        b2 = model.cache_spec(batch=2, max_len=16)
        s2 = model.cache_spec(batch=1, max_len=32)
        axes = {}
        for key in b1:
            d_b = [i for i, (a, b) in enumerate(zip(b1[key].shape,
                                                    b2[key].shape)) if a != b]
            d_s = [i for i, (a, b) in enumerate(zip(b1[key].shape,
                                                    s2[key].shape)) if a != b]
            assert len(d_b) == 1, f"cache[{key}]: ambiguous batch axis {d_b}"
            assert len(d_s) <= 1, f"cache[{key}]: ambiguous seq axis {d_s}"
            # store seq axis negative so it survives batch-rank differences
            ndim = len(b1[key].shape)
            seq = (d_s[0] - ndim) if d_s else None
            axes[key] = (d_b[0] - ndim, seq)
        return axes

    def _resize_leaf(self, x: jax.Array, key: str, batch: int,
                     extent: int) -> jax.Array:
        """Pad/slice one cache leaf to (batch, extent) on its own axes."""
        b_ax, s_ax = self._axes[key]
        for ax, target, value in ((b_ax, batch, 0.0),
                                  (s_ax, extent, _SEQ_PAD.get(key, 0.0))):
            if ax is None:
                continue
            cur = x.shape[ax]
            if target > cur:
                pads = [(0, 0)] * x.ndim
                pads[ax] = (0, target - cur)
                x = jnp.pad(x, pads, constant_values=value)
            elif target < cur:
                idx = [slice(None)] * x.ndim
                idx[ax] = slice(0, target)
                x = x[tuple(idx)]
        return x

    def _resize_cache(self) -> None:
        """Track the slot-bucket batch dim and the pool's high-water
        extent; shrink when retirements lower either (the compile cache
        then reuses the smaller step)."""
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            self._cache, self._batch, self._extent = None, 0, 0
            return
        batch = next(b for b in self.slot_buckets if b >= max(live) + 1)
        extent = self.pool.extent()
        if (batch, extent) == (self._batch, self._extent):
            return
        self._cache = {k: self._resize_leaf(v, k, batch, extent)
                       for k, v in self._cache.items()}
        self._batch, self._extent = batch, extent

    def _write_slot(self, slot: int, row_cache: PyTree, plen: int) -> None:
        """Copy one prefilled request (batch row 0 of ``row_cache``) into
        ``slot`` of the live cache, padded out to the current extent."""
        for key, leaf in self._cache.items():
            b_ax, _ = self._axes[key]
            row = self._resize_leaf(row_cache[key], key, 1, self._extent)
            idx = [slice(None)] * leaf.ndim
            idx[b_ax] = slot
            row_idx = [slice(None)] * row.ndim
            row_idx[b_ax] = 0
            self._cache[key] = leaf.at[tuple(idx)].set(row[tuple(row_idx)])

    # ------------------------------------------------------------- stepping

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _live(self) -> List[Tuple[int, fe.Request]]:
        return [(i, r) for i, r in enumerate(self.slots) if r is not None]

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _expire(self) -> None:
        now = time.perf_counter()
        expired = [r for r in self.eng._pending if r.expired(now)]
        if expired:
            for r in expired:
                r.state = fe.EXPIRED
            self.eng._pending = [r for r in self.eng._pending
                                 if r.state == fe.QUEUED]

    def _admission_group(self) -> List[fe.Request]:
        """Pick this step's prefill group: queued requests in strict
        (priority desc, id asc) order; the head request sets the prompt
        length (one prefill shape per launch) and same-length followers
        join up to the free-slot / bucket / KV-pool / prefill-chunk caps."""
        free = self._free_slots()
        if not free or not self.eng._pending:
            return []
        # retried requests wait out their backoff window before re-admission
        now = time.perf_counter()
        queue = sorted((r for r in self.eng._pending if r.retry_at <= now),
                       key=lambda r: (-r.priority, r.id))
        cap = min(len(free), self.slot_buckets[-1],
                  self.eng.scfg.buckets[-1])
        chunk = self.eng.scfg.prefill_chunk
        group: List[fe.Request] = []
        budget_tokens = 0
        # simulate pool reservations so the group stays admissible jointly
        need = 0
        for r in queue:
            if group and r.prompt_len != group[0].prompt_len:
                continue      # next step's head may pick this length
            tokens = r.prompt_len + r.num_tokens
            if len(group) == cap:
                break
            if chunk and group and budget_tokens + r.prompt_len > chunk:
                break
            if self.pool.blocks_needed(tokens) + need > self.pool.free_blocks:
                if not group:
                    continue  # head doesn't fit yet; try a smaller request
                break
            group.append(r)
            need += self.pool.blocks_needed(tokens)
            budget_tokens += r.prompt_len
        return group

    def _admit(self) -> None:
        group = self._admission_group()
        if not group:
            return
        plen = group[0].prompt_len
        bucket = next(b for b in self.eng.scfg.buckets if b >= len(group))
        with span("serve.admit", n=len(group), bucket=bucket, plen=plen):
            self._admit_group(group, plen, bucket)

    def _admit_group(self, group: List[fe.Request], plen: int,
                     bucket: int) -> None:
        ids = {r.id for r in group}
        self.eng._pending = [r for r in self.eng._pending
                             if r.id not in ids]
        now = time.perf_counter()
        for r in group:
            r.slot = self._free_slots()[0]
            self.pool.alloc(r.slot, plen + r.num_tokens)
            self.slots[r.slot] = r
            r.state = fe.RUNNING
            r.admit_t, r.admit_tick = now, self.eng.ticks
        with span("serve.prefill"):
            toks = jnp.stack([r.payload for r in group])
            if bucket > len(group):
                toks = jnp.pad(toks, ((0, bucket - len(group)), (0, 0)))
            # attempt counter advances BEFORE the launch (fault included),
            # so a retried step moves past a one-shot injected fault index
            attempt = self._prefill_calls
            self._prefill_calls += 1
            if self.policy is not None and self.policy.injector is not None:
                # after slot/pool assignment, so recovery sees the group live
                self.policy.injector.maybe_fail_prefill(attempt)
            with self.eng._mesh_ctx():
                logits, pre_cache = self.eng._prefill(self.eng.params,
                                                      {"tokens": toks})
            self.eng.ticks += 1
            logits, bad_rows = self._guard_logits(logits, group)
            tok0 = self.eng._select(logits, self._next_key())
        with span("serve.prefill_sync"):
            tok0 = np.asarray(tok0)
        first_t = time.perf_counter()
        with span("serve.cache_write"):
            # grow the live cache geometry BEFORE inserting the new rows
            if self._cache is None:
                extent = self.pool.extent()
                batch = next(b for b in self.slot_buckets
                             if b >= max(r.slot for r in group) + 1)
                spec = self.eng.model.cache_spec(batch=batch, max_len=extent)
                self._cache = {k: jnp.zeros(v.shape, v.dtype)
                               for k, v in spec.items()}
                for key, pad in _SEQ_PAD.items():
                    if key in self._cache and pad != 0.0:
                        self._cache[key] = jnp.full(
                            self._cache[key].shape, pad,
                            self._cache[key].dtype)
                self._batch, self._extent = batch, extent
            else:
                self._resize_cache()
            for i, r in enumerate(group):
                if i in bad_rows:
                    self.eng._fault_event("nan_quarantined", id=r.id,
                                          at="prefill")
                    self._requeue_or_fail(r, "non-finite logits at prefill")
                    continue
                r.out.append(int(tok0[i]))
                r.first_token_t = first_t
                if len(r.out) >= r.num_tokens:
                    self._retire(r)   # single-token request: done at prefill
                else:
                    row = {k: jnp.take(v, jnp.array([i]),
                                       axis=self._axes[k][0])
                           for k, v in pre_cache.items()}
                    self._write_slot(r.slot, row, plen)
            self._resize_cache()      # a same-step retirement may shrink

    def _retire(self, req: fe.Request) -> None:
        self.slots[req.slot] = None
        self.pool.free(req.slot)
        req.slot = None
        req.state = fe.DONE
        req.result = np.asarray(req.out, dtype=np.int32)
        req.finish_t = time.perf_counter()
        req.finish_tick = self.eng.ticks
        live = sum(r is not None for r in self.slots) + 1
        self.eng._log_request(
            id=req.id,
            latency_ms=(req.finish_t - req.submit_t) * 1e3,
            queue_wait_ms=(req.admit_t - req.submit_t) * 1e3,
            ttft_ms=(req.first_token_t - req.submit_t) * 1e3,
            decode_ms=(req.finish_t - req.admit_t) * 1e3,
            latency_ticks=req.finish_tick - req.submit_tick,
            queue_wait_ticks=req.admit_tick - req.submit_tick,
            bucket=self._batch or live,
            batch_fill=live / self.capacity,
            prompt_len=req.prompt_len,
            decode_tokens=req.num_tokens,
        )

    def _decode_once(self) -> None:
        live = self._live()
        if not live:
            return
        pol = self.policy
        attempt = self._decode_calls       # advances even on a failed
        self._decode_calls += 1            # launch — see _admit
        if pol is not None and pol.injector is not None:
            pol.injector.maybe_fail_decode(attempt)
        b = self._batch
        with span("serve.decode", live=len(live), rows=b,
                  extent=self._extent):
            tok = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            for slot, r in live:
                tok[slot, 0] = r.out[-1]
                pos[slot] = r.prompt_len + len(r.out) - 1
            if self._timer is not None:
                self._timer.start()
            with self.eng._mesh_ctx():
                logits, self._cache = self.eng._decode(
                    self.eng.params, jnp.asarray(tok), jnp.asarray(pos),
                    self._cache)
            self.eng.ticks += 1
            if self._timer is not None:
                self._watch(logits, attempt)
            rows: List[Optional[fe.Request]] = [None] * b
            for slot, r in live:
                rows[slot] = r
            logits, bad_rows = self._guard_logits(logits, rows)
            nxt = self.eng._select(logits, self._next_key())
        with span("serve.token_sync"):
            nxt = np.asarray(nxt)
        with span("serve.retire"):
            for slot, r in live:
                if slot in bad_rows:
                    # row-independence makes surviving rows' cache writes
                    # valid; only this request's state is junk
                    self.eng._fault_event("nan_quarantined", id=r.id,
                                          at="decode")
                    self._requeue_or_fail(r, "non-finite logits at decode")
                    continue
                r.out.append(int(nxt[slot]))
                if len(r.out) >= r.num_tokens:
                    self._retire(r)
            self._resize_cache()

    def _watch(self, logits, attempt: int) -> None:
        """Watchdog (fault policy): time the decode launch to logits-ready
        and flag stragglers; raise on a timeout that counts as a fault."""
        pol = self.policy
        # the launch is async; time to logits-ready, which the token
        # select forces anyway
        with span("serve.watchdog_sync"):
            jax.block_until_ready(logits)
        flagged = len(self._timer.straggler_steps)
        dt = self._timer.stop(attempt)
        if len(self._timer.straggler_steps) > flagged:
            self.eng._fault_counters["straggler_steps"] += 1
        if pol.step_timeout_s and dt > pol.step_timeout_s:
            self.eng._fault_event("watchdog_timeouts",
                                  step=attempt, dt_s=dt)
            if pol.timeout_is_fault:
                # before any token lands: recovery replays the whole
                # step, so no request observes a half-applied step
                raise StepTimeout(
                    f"decode launch {attempt} took "
                    f"{dt:.3f}s > step_timeout_s={pol.step_timeout_s}")

    # --------------------------------------- fault handling (§10; policy)

    def _guard_logits(self, logits, rows: List[Optional[fe.Request]]):
        """NaN/Inf quarantine + deterministic poison injection.

        ``rows[i]`` is the request owning logits row ``i`` (None for
        padding).  Returns ``(logits, bad_rows)`` where ``bad_rows`` are
        the indices whose request must be quarantined; their rows are
        zeroed so the batch's token select stays well-defined (the
        quarantined requests never consume the selected token).
        """
        pol = self.policy
        if pol is None:
            return logits, set()
        inj = pol.injector
        poison = [i for i, r in enumerate(rows)
                  if r is not None and inj is not None
                  and inj.poison_request(r.id)]
        if not pol.nan_guard and not poison:
            return logits, set()
        with span("serve.guard_sync"):
            host = np.asarray(logits).copy()
        for i in poison:
            host[i] = np.nan
        bad_rows: set = set()
        if pol.nan_guard:
            for i, r in enumerate(rows):
                if r is not None and not np.isfinite(
                        host[i].astype(np.float32)).all():
                    bad_rows.add(i)
                    host[i] = 0.0
        return jnp.asarray(host), bad_rows

    def _requeue_or_fail(self, req: fe.Request, reason: str) -> None:
        """Bounded-retry recovery for one request: free its slot/KV, then
        either requeue it for full replay (with an exponential-backoff
        window) or mark it terminally FAILED.

        Replay restarts from the prompt (``out`` resets): re-prefilling
        ``prompt + generated-prefix`` as one longer sequence would change
        the matmul M extent and with it the f32 reduction order, breaking
        bit-exactness (see core/sac.py).  Greedy replay regenerates the
        identical prefix, so ``stream()`` consumers — whose emitted
        counter simply stalls until ``out`` regrows — never see a torn or
        divergent token sequence.
        """
        if req.slot is not None:
            self.slots[req.slot] = None
            self.pool.free(req.slot)
            req.slot = None
        req.retries += 1
        if req.retries > self.policy.max_retries:
            req.state = fe.FAILED
            req.error = reason
            req.finish_t = time.perf_counter()
            req.finish_tick = self.eng.ticks
            self.eng._fault_event("failed_requests", id=req.id,
                                  reason=reason, retries=req.retries - 1)
            return
        req.out = []
        req.state = fe.QUEUED
        req.retry_at = (time.perf_counter()
                        + self.policy.backoff_for(req.retries))
        self.eng._fault_event("retries", id=req.id, reason=reason,
                              attempt=req.retries)
        if all(p.id != req.id for p in self.eng._pending):
            self.eng._pending.append(req)

    def _lose_slots(self) -> None:
        """Injected device-state loss: the slot's cache rows are junk, so
        the owning request replays; everything else is untouched."""
        pol = self.policy
        if pol is None or pol.injector is None:
            return
        hit = False
        for slot in pol.injector.lost_slots(self._step_idx):
            r = self.slots[slot] if slot < len(self.slots) else None
            if r is not None:
                self.eng._fault_event("slot_losses", id=r.id, slot=slot)
                self._requeue_or_fail(r, f"slot {slot} device state lost")
                hit = True
        if hit:
            self._resize_cache()

    def _recover(self, exc: Exception) -> None:
        """Engine-step failure: requeue-or-fail every in-flight request
        and rebuild the execution state from scratch.

        The decode jit donates the cache (``donate_argnums``), so a
        launch that raised may have invalidated it — nothing step-level
        is salvageable.  The slot table, KV pool, and live cache all
        reset; surviving requests re-admit through the normal path and
        replay bit-exactly (see :meth:`_requeue_or_fail`).  Repeated
        faults demote the engine impl down the policy's fallback ladder
        (pallas -> planes preserves bit-exactness; planes -> float trades
        it for availability).
        """
        self._fault_streak += 1
        self.eng._fault_event("recoveries",
                              error=f"{type(exc).__name__}: {exc}",
                              streak=self._fault_streak)
        for _, r in self._live():
            self._requeue_or_fail(r, f"engine step failed: "
                                     f"{type(exc).__name__}: {exc}")
        self.slots = [None] * self.capacity
        self.pool.release_all()     # a mid-step exception may have left
        self._cache = None          # per-slot bookkeeping half-updated
        self._batch = self._extent = 0
        if self._fault_streak >= self.policy.demote_after:
            if self.eng._demote_impl(
                    f"{self._fault_streak} consecutive step faults "
                    f"(last: {type(exc).__name__})"):
                self._fault_streak = 0

    def _maybe_wait_backoff(self) -> None:
        """With nothing in flight and every queued request inside its
        backoff window, sleep to the earliest retry so the step loop
        stays productive instead of spinning."""
        if any(r is not None for r in self.slots) or not self.eng._pending:
            return
        wait = min(r.retry_at for r in self.eng._pending) \
            - time.perf_counter()
        if wait > 0:
            time.sleep(min(wait, self.policy.backoff_cap_s))

    def step(self) -> bool:
        """One scheduler step: expire -> admit (one prefill group) -> one
        decode launch over the slot table -> retire.  Returns True if any
        request is still queued or in flight.

        With a fault policy, the step body runs under the recovery
        umbrella: any exception requeues in-flight work (bounded retries,
        then FAILED) and rebuilds the slot table — the loop itself never
        dies to a step fault.
        """
        with span("serve.step"):
            if self.policy is None:
                self._expire()
                self._admit()
                self._decode_once()
            else:
                try:
                    self._expire()
                    self._lose_slots()
                    self._admit()
                    self._decode_once()
                    self._fault_streak = 0   # clean step: demotion de-arms
                except Exception as exc:  # noqa: BLE001 — any step fault
                    self._recover(exc)       # enters bounded recovery
                self._step_idx += 1
                self._maybe_wait_backoff()
        return bool(self.eng._pending or any(r is not None
                                             for r in self.slots))

    def cancel(self, req: fe.Request) -> bool:
        if req.state == fe.QUEUED:
            req.state = fe.CANCELLED
            self.eng._pending = [r for r in self.eng._pending
                                 if r.id != req.id]
            return True
        if req.state == fe.RUNNING:
            # mid-decode withdrawal: the slot and its KV blocks free NOW;
            # the abandoned cache rows are masked junk to every other row
            self.slots[req.slot] = None
            self.pool.free(req.slot)
            req.slot = None
            req.state = fe.CANCELLED
            self._resize_cache()
            return True
        return False

    # ----------------------------------------------------------- blocking

    def run_until(self, req: fe.Request) -> None:
        """Step until ``req`` leaves the queued/running states."""
        while req.state in (fe.QUEUED, fe.RUNNING):
            if not self.step():
                break

    def drain(self) -> Dict[int, jax.Array]:
        """Compatibility wrapper: run the step loop until every request
        pending at call time has finished; returns {id: tokens} exactly
        like the batch path (cancelled/expired requests excluded)."""
        wave = ([r for r in self.eng._pending]
                + [r for _, r in self._live()])
        while self.step():
            pass
        return {r.id: jnp.asarray(r.result) for r in wave
                if r.state == fe.DONE}

    def stream(self, req: fe.Request) -> Iterator[int]:
        """Per-token iterator: drives the scheduler only as far as needed
        for the next token of ``req``."""
        emitted = 0
        while True:
            while emitted < len(req.out):
                yield req.out[emitted]
                emitted += 1
            if req.state in fe.TERMINAL:
                # FAILED raises even mid-stream: replay retracted the
                # emitted prefix, so a silent stop would look like a
                # short-but-valid completion
                if req.state == fe.FAILED or \
                        (req.state != fe.DONE and emitted == 0):
                    self.eng._finished_result(req)   # raise the right error
                return
            # a queued/running request always keeps step() productive
            # (queued => pending non-empty), so this cannot spin idle;
            # the retiring step may return False with tokens still
            # unflushed, hence the loop-back before any exit
            self.step()
