"""Chaos suite for the serving fault policy (docs/DESIGN.md §10).

The load-bearing guarantee is the acceptance bar of the resilience PR:
under injected faults — a kernel exception at a chosen decode step, a
persistently NaN-poisoned request, a corrupted kneaded plane repaired by
re-knead — every *surviving* request's drain() output is **bit-identical**
to a fault-free run, on the planes and pallas impls alike, while the
injected request fails within ``max_retries`` and ``latency_stats()``
reports the retry/straggler/degradation counters.  Around that: the
NaN-logit quarantine (transient vs persistent), retry exhaustion and the
``RequestFailed`` error surface, cancel and deadline expiry during a
retry-backoff window, the graceful-degradation ladder, slot-loss
recovery, kneaded-weight checksum verification + repair, checkpoint
per-leaf CRCs, and the training restart-loop backoff fixes.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ckpt
from repro.configs.registry import get_config
from repro.core.kneading import (KneadedIntegrityError, knead_padded,
                                 reknead_like)
from repro.core.schedule import shard_schedule
from repro.inference.engine import ServingConfig, ServingEngine
from repro.inference.frontend import DeadlineExceeded, RequestFailed
from repro.inference.kv_pool import KVBlockPool
from repro.inference.resilience import (EngineFaultInjector,
                                        ServingFaultPolicy, corrupt_kneaded)
from repro.models.lm import LanguageModel
from repro.runtime import fault_tolerance as ft

MIN_DIM = 8      # knead smoke-size projections too


@pytest.fixture(scope="module")
def smol():
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _engine(smol, impl="float", **kw):
    cfg, params = smol
    defaults = dict(max_len=48, impl=impl, knead_min_dim=MIN_DIM,
                    buckets=(1, 2, 4), scheduler="continuous",
                    max_inflight=3, kv_block=16)
    defaults.update(kw)
    return ServingEngine(cfg, params, ServingConfig(**defaults))


def _submit_set(eng, cfg, spec=((6, 5), (6, 3), (9, 4))):
    handles = []
    for i, (plen, n) in enumerate(spec):
        toks = jax.random.randint(jax.random.PRNGKey(50 + i), (plen,), 0,
                                  cfg.vocab_size)
        handles.append(eng.submit(toks, n))
    return handles


def _policy(**kw):
    defaults = dict(max_retries=2, retry_backoff_s=0.005)
    defaults.update(kw)
    return ServingFaultPolicy(**defaults)


# ------------------------------------------------- step-fault recovery


@pytest.mark.parametrize("impl", ["float", "pallas"])
def test_decode_fault_recovery_bit_identical(smol, impl):
    """An injected kernel exception mid-decode requeues every in-flight
    request; the replayed generations match a fault-free run bitwise."""
    cfg, _ = smol
    ref = _engine(smol, impl=impl)
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, impl=impl, fault_policy=_policy(
        injector=EngineFaultInjector(fail_decode_steps=(2,))))
    handles = _submit_set(eng, cfg)
    got = eng.drain()
    assert sorted(got) == sorted(want)
    for rid in want:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    stats = eng.latency_stats()
    assert stats["recoveries"] == 1 and stats["retries"] >= 1
    assert all(h.state == "done" for h in handles)


@pytest.mark.parametrize("impl", ["float", "pallas"])
def test_prefill_fault_recovery(smol, impl):
    cfg, _ = smol
    ref = _engine(smol, impl=impl)
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, impl=impl, fault_policy=_policy(
        injector=EngineFaultInjector(fail_prefill_steps=(0,))))
    _submit_set(eng, cfg)
    got = eng.drain()
    for rid in want:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    assert eng.latency_stats()["recoveries"] == 1


@pytest.mark.parametrize("impl", ["float", "pallas"])
def test_slot_loss_recovery(smol, impl):
    """Simulated loss of one slot's device state replays only that
    request; everything else decodes on undisturbed."""
    cfg, _ = smol
    ref = _engine(smol, impl=impl)
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, impl=impl, fault_policy=_policy(
        injector=EngineFaultInjector(lose_slot_steps=((1, 0),))))
    _submit_set(eng, cfg)
    got = eng.drain()
    for rid in want:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    stats = eng.latency_stats()
    assert stats["slot_losses"] == 1
    assert stats.get("recoveries", 0) == 0     # zero counters are omitted


# ---------------------------------------------------- NaN quarantine


def test_nan_quarantine_only_offending_request(smol):
    """A persistently NaN-poisoned request FAILs within max_retries;
    its batchmates' outputs stay bit-identical to a fault-free run."""
    cfg, _ = smol
    ref = _engine(smol)
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, fault_policy=_policy(
        injector=EngineFaultInjector(nan_request_ids=(1,))))
    handles = _submit_set(eng, cfg)
    got = eng.drain()
    assert sorted(got) == [0, 2]
    for rid in got:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    assert handles[1].state == "failed"
    assert handles[1].retries == 3          # max_retries=2 + the final try
    assert "non-finite" in handles[1].error
    with pytest.raises(RequestFailed, match="request 1 failed"):
        handles[1].result()
    stats = eng.latency_stats()
    assert stats["nan_quarantined"] == 3 and stats["failed_requests"] == 1


def test_nan_transient_recovers(smol):
    """nan_once models a transient glitch: the retry replays cleanly and
    the request completes bit-identically."""
    cfg, _ = smol
    ref = _engine(smol)
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, fault_policy=_policy(
        injector=EngineFaultInjector(nan_request_ids=(0,), nan_once=True)))
    handles = _submit_set(eng, cfg)
    got = eng.drain()
    assert sorted(got) == [0, 1, 2]
    for rid in want:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    assert handles[0].retries == 1


# ------------------------------------------- retries, backoff, deadlines


def test_retry_exhaustion_fails_terminally(smol):
    cfg, _ = smol
    eng = _engine(smol, fault_policy=_policy(
        max_retries=1,
        injector=EngineFaultInjector(nan_request_ids=(0,))))
    toks = jax.random.randint(jax.random.PRNGKey(1), (6,), 0,
                              cfg.vocab_size)
    h = eng.submit(toks, 4)
    assert eng.drain() == {}
    assert h.state == "failed" and h.retries == 2
    # FAILED is terminal: not cancellable, not re-queued
    assert not h.cancel()
    assert not eng.scheduler_step()


def test_cancel_during_retry_backoff(smol):
    """A request sitting out its backoff window is still QUEUED — cancel
    withdraws it before the retry fires."""
    cfg, _ = smol
    eng = _engine(smol, fault_policy=_policy(
        retry_backoff_s=30.0,     # parks the retry far in the future
        injector=EngineFaultInjector(fail_prefill_steps=(0,))))
    toks = jax.random.randint(jax.random.PRNGKey(1), (6,), 0,
                              cfg.vocab_size)
    h = eng.submit(toks, 4)
    eng.scheduler_step()          # fault -> requeued with retry_at set
    assert h.state == "queued" and h.retries == 1
    assert h.cancel()
    assert h.state == "cancelled"
    assert not eng.scheduler_step()


def test_deadline_expires_during_backoff(smol):
    """Deadlines keep applying to re-queued requests: a retry parked
    past its deadline expires instead of replaying."""
    cfg, _ = smol
    eng = _engine(smol, fault_policy=_policy(
        retry_backoff_s=0.05,
        injector=EngineFaultInjector(fail_prefill_steps=(0,))))
    toks = jax.random.randint(jax.random.PRNGKey(1), (6,), 0,
                              cfg.vocab_size)
    h = eng.submit(toks, 4, deadline=0.02)
    eng.scheduler_step()          # fault -> backoff window > deadline
    time.sleep(0.03)
    eng.scheduler_step()
    assert h.state == "expired"
    with pytest.raises(DeadlineExceeded):
        h.result()


def test_backoff_window_delays_readmission(smol):
    cfg, _ = smol
    pol = _policy(retry_backoff_s=0.05, backoff_mult=3.0, backoff_cap_s=0.1)
    assert pol.backoff_for(1) == pytest.approx(0.05)
    assert pol.backoff_for(2) == pytest.approx(0.1)    # capped, not 0.15
    eng = _engine(smol, fault_policy=dataclasses.replace(
        pol, injector=EngineFaultInjector(fail_prefill_steps=(0,))))
    toks = jax.random.randint(jax.random.PRNGKey(1), (6,), 0,
                              cfg.vocab_size)
    h = eng.submit(toks, 2)
    t0 = time.perf_counter()
    eng.drain()
    assert time.perf_counter() - t0 >= 0.05    # sat out the window
    assert h.state == "done" and h.retries == 1


# ------------------------------------------------------------ watchdog


def test_watchdog_flags_slow_steps(smol):
    """step_timeout_s far below any real launch time: every decode step
    counts a watchdog timeout (surfaced via latency_stats), and with
    timeout_is_fault=False the work still completes."""
    cfg, _ = smol
    eng = _engine(smol, fault_policy=_policy(step_timeout_s=1e-9))
    _submit_set(eng, cfg)
    got = eng.drain()
    assert sorted(got) == [0, 1, 2]
    assert eng.latency_stats()["watchdog_timeouts"] >= 1


def test_watchdog_timeout_as_fault_exhausts_retries(smol):
    """timeout_is_fault escalates every (always-slow) step to the
    recovery path until retries exhaust — requests FAIL, loop survives."""
    cfg, _ = smol
    eng = _engine(smol, fault_policy=_policy(
        max_retries=1, step_timeout_s=1e-9, timeout_is_fault=True))
    toks = jax.random.randint(jax.random.PRNGKey(1), (6,), 0,
                              cfg.vocab_size)
    h = eng.submit(toks, 4)
    assert eng.drain() == {}
    assert h.state == "failed"
    stats = eng.latency_stats()
    assert stats["watchdog_timeouts"] >= 2 and stats["recoveries"] >= 2


# ------------------------------------------------- degradation ladder


def test_demotion_pallas_to_planes_bit_exact(smol):
    """Two consecutive step faults demote pallas -> planes; since planes
    is the kernel's bitwise oracle, the completed generations still match
    the fault-free pallas reference exactly."""
    cfg, _ = smol
    ref = _engine(smol, impl="pallas")
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, impl="pallas", fault_policy=_policy(
        max_retries=3, demote_after=2,
        injector=EngineFaultInjector(fail_decode_steps=(1, 2))))
    _submit_set(eng, cfg)
    got = eng.drain()
    assert eng.scfg.impl == "planes" and eng.cfg.impl == "planes"
    assert sorted(got) == sorted(want)
    for rid in want:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    stats = eng.latency_stats()
    assert stats["degradations"] == 1 and stats["recoveries"] == 2
    events = [e for e in eng.fault_events() if e["kind"] == "degradations"]
    assert events[0]["impl_from"] == "pallas"
    assert events[0]["impl_to"] == "planes"


def test_demotion_ladder_ends(smol):
    """Demotion stops at the ladder's last rung instead of cycling."""
    eng = _engine(smol, impl="planes",
                  fault_policy=_policy(fallback_impls=("planes", "float")))
    assert eng._demote_impl("test") and eng.scfg.impl == "float"
    assert not eng._demote_impl("test")    # no rung below float
    assert eng.scfg.impl == "float"


def test_demotion_replaces_only_impl(smol):
    """The pallas -> planes ladder replaces only ``impl``: every other
    ``ServingConfig`` and ``ModelConfig`` field is unchanged after the
    demotion two step faults trigger."""
    cfg, _ = smol
    eng = _engine(smol, impl="pallas", fault_policy=_policy(
        max_retries=3, demote_after=2,
        injector=EngineFaultInjector(fail_decode_steps=(1, 2))))
    scfg0, cfg0 = eng.scfg, eng.cfg
    handles = _submit_set(eng, cfg)
    eng.drain()
    assert all(h.state == "done" for h in handles)
    assert eng.latency_stats()["degradations"] == 1
    assert eng.scfg == dataclasses.replace(scfg0, impl="planes")
    assert eng.cfg == dataclasses.replace(cfg0, impl="planes")


# ------------------------------------------- kneaded-weight integrity


def test_kneaded_checksums_detect_corruption():
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    kw = knead_padded(w, bits=4, ks=16, n_block=16)
    assert kw.verify() == ()
    for field in ("occupancy", "planes", "schedule.counts",
                  "schedule.plane_bits"):
        bad = corrupt_kneaded(kw, field, flat_index=1)
        assert bad.verify() == (field,)
        with pytest.raises(KneadedIntegrityError, match=field):
            bad.verify(strict=True)


def test_reknead_repairs_bit_identically():
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    kw = knead_padded(w, bits=4, ks=16, n_block=16)
    bad = corrupt_kneaded(kw, "planes", flat_index=2)
    fixed = reknead_like(bad, w)
    assert fixed.verify() == ()
    for field in ("planes", "signs", "scale", "occupancy"):
        assert np.array_equal(np.asarray(getattr(fixed, field)),
                              np.asarray(getattr(kw, field))), field
    assert fixed.checksums == kw.checksums


def test_sharded_checksums_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 64))
    skw = shard_schedule(knead_padded(w, bits=4, ks=16, n_block=16), 2)
    assert skw.verify() == ()
    bad = dataclasses.replace(
        skw, counts=jnp.asarray(np.asarray(skw.counts) + 1))
    assert "counts" in bad.verify()


def test_engine_verify_weights_repairs(smol):
    """Corrupt one kneaded plane inside a live engine; verify_weights
    re-kneads it from the retained float checkpoint and subsequent
    serving is bit-identical to an untouched engine."""
    from repro.core.kneading import KneadedWeight

    cfg, _ = smol
    ref = _engine(smol, impl="planes")
    _submit_set(ref, cfg)
    want = ref.drain()
    eng = _engine(smol, impl="planes", fault_policy=_policy())
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        eng.params, is_leaf=lambda x: isinstance(x, KneadedWeight))
    leaves, hit = [], False
    for _, leaf in flat:
        if isinstance(leaf, KneadedWeight) and not hit:
            leaf, hit = corrupt_kneaded(leaf, "planes", flat_index=3), True
        leaves.append(leaf)
    assert hit
    eng.params = jax.tree_util.tree_unflatten(treedef, leaves)
    report = eng.verify_weights()
    assert len(report) == 1 and report[0]["repaired"]
    assert report[0]["fields"] == ("planes",)
    assert eng.verify_weights() == []          # clean after repair
    _submit_set(eng, cfg)
    got = eng.drain()
    for rid in want:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    assert eng.latency_stats()["integrity_repairs"] == 1


# ------------------------------------------------ checkpoint integrity


def _save_tree(tmp_path):
    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8),
            "b": np.ones(8, dtype=np.float32)}
    d = ckpt.save(tmp_path, 3, tree)
    return tree, d


def test_checkpoint_crc_in_manifest(tmp_path):
    import json
    tree, d = _save_tree(tmp_path)
    manifest = json.loads((d / "manifest.json").read_text())
    assert all("crc32" in leaf for leaf in manifest["leaves"])
    out = ckpt.restore(tmp_path, 3, tree)
    assert np.array_equal(np.asarray(out["w"]), tree["w"])


def test_checkpoint_bitflip_detected(tmp_path):
    tree, d = _save_tree(tmp_path)
    leaf = d / "leaf_0.npy"
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0x40                    # flip a payload bit
    leaf.write_bytes(bytes(raw))
    with pytest.raises(ckpt.CheckpointCorrupt, match="leaf 0"):
        ckpt.restore(tmp_path, 3, tree)


def test_checkpoint_truncation_detected(tmp_path):
    tree, d = _save_tree(tmp_path)
    leaf = d / "leaf_1.npy"
    leaf.write_bytes(leaf.read_bytes()[:40])    # torn write
    with pytest.raises(ckpt.CheckpointCorrupt, match="leaf 1"):
        ckpt.restore(tmp_path, 3, tree)


def test_checkpoint_leaf_count_mismatch(tmp_path):
    tree, _ = _save_tree(tmp_path)
    bigger = dict(tree, extra=np.zeros(3, np.float32))
    with pytest.raises(ckpt.CheckpointCorrupt, match="leaves"):
        ckpt.restore(tmp_path, 3, bigger)


# ------------------------------------------------ restart-loop backoff


def test_restart_backoff_resets_after_clean_step():
    sleeps = []
    pol = ft.RestartPolicy(max_restarts=10, backoff_s=1.0, backoff_mult=2.0)
    fails = {2: 2, 5: 1}                    # step -> remaining failures

    def step_fn(step):
        if fails.get(step, 0):
            fails[step] -= 1
            raise RuntimeError("boom")

    import repro.runtime.fault_tolerance as mod
    orig = mod.time.sleep
    mod.time.sleep = sleeps.append
    try:
        restarts = ft.run_resilient_loop(
            start_step=0, num_steps=8, step_fn=step_fn,
            restore_fn=lambda: 2, policy=pol)
    finally:
        mod.time.sleep = orig
    assert restarts == 3
    # consecutive faults at step 2 escalate (1, 2); the clean steps in
    # between reset the fault at step 5 back to the base backoff
    assert sleeps == [1.0, 2.0, 1.0]


def test_restart_backoff_cap_and_jitter():
    pol = ft.RestartPolicy(backoff_s=1.0, backoff_cap_s=4.0,
                           jitter_frac=0.5)
    assert pol.next_backoff(3.0) == pytest.approx(4.0)   # capped
    import random
    rng = random.Random(0)
    s = pol.sleep_s(100.0, rng=rng)
    assert 4.0 <= s <= 6.0            # cap first, then <= 50% jitter


def test_restart_loop_default_policy_not_shared():
    """policy=None builds a fresh default per call (the old shared
    mutable-default instance leaked state across callers)."""
    calls = []

    def flaky(step):
        calls.append(step)

    for _ in range(2):
        assert ft.run_resilient_loop(
            start_step=0, num_steps=2, step_fn=flaky,
            restore_fn=lambda: 0) == 0


# ------------------------------------------------------------ KV pool


def test_kv_pool_release_all():
    pool = KVBlockPool(num_slots=3, max_len=64, block=16)
    pool.alloc(0, 40)
    pool.alloc(1, 10)
    assert pool.used_blocks == 4
    assert pool.release_all() == 4
    assert pool.used_blocks == 0 and pool.extent() == 0
    pool.alloc(0, 16)                  # table usable again
    assert pool.used_blocks == 1


# ---------------------------------------------------- CNN NaN guard


def test_cnn_nan_guard_fails_only_bad_request():
    from repro.inference.cnn_engine import CNNServingConfig, CNNServingEngine
    from repro.models import cnn

    cfg = dataclasses.replace(cnn.CNN_ZOO["nin"], image_size=16)
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    eng = CNNServingEngine(cfg, params, CNNServingConfig(
        impl="float", buckets=(1, 2, 4), jit=False,
        fault_policy=ServingFaultPolicy()))
    good = jax.random.normal(jax.random.PRNGKey(1), (16, 16, 3))
    bad = jnp.full((16, 16, 3), jnp.nan)
    h_good, h_bad = eng.submit(good), eng.submit(bad)
    results = eng.drain()
    assert h_good in results and h_bad not in results
    assert h_bad.state == "failed"
    with pytest.raises(RequestFailed):
        h_bad.result()
    assert eng.latency_stats()["nan_quarantined"] == 1


# ------------------------------------------------- acceptance (chaos)


@pytest.mark.parametrize("impl", ["planes", "pallas"])
def test_chaos_acceptance(smol, impl):
    """The ISSUE's acceptance bar, per impl: kernel exception at a chosen
    step + a persistently-NaN request + a corrupted plane repaired by
    re-knead, all in one run — survivors bit-identical to fault-free,
    the poisoned request FAILED within max_retries, counters reported."""
    from repro.core.kneading import KneadedWeight

    cfg, _ = smol
    ref = _engine(smol, impl=impl)
    _submit_set(ref, cfg)
    want = ref.drain()

    eng = _engine(smol, impl=impl, fault_policy=_policy(
        max_retries=2, demote_after=99,     # no demotion: isolate recovery
        injector=EngineFaultInjector(fail_decode_steps=(2,),
                                     nan_request_ids=(1,))))
    # corrupt one kneaded plane, then let the integrity path repair it
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        eng.params, is_leaf=lambda x: isinstance(x, KneadedWeight))
    leaves, hit = [], False
    for _, leaf in flat:
        if isinstance(leaf, KneadedWeight) and not hit:
            leaf, hit = corrupt_kneaded(leaf, "planes", flat_index=5), True
        leaves.append(leaf)
    eng.params = jax.tree_util.tree_unflatten(treedef, leaves)
    assert len(eng.verify_weights()) == 1

    handles = _submit_set(eng, cfg)
    got = eng.drain()
    assert sorted(got) == [0, 2]               # the poisoned request fell out
    for rid in got:
        assert np.array_equal(np.asarray(got[rid]), np.asarray(want[rid]))
    assert handles[1].state == "failed"
    assert handles[1].retries <= 2 + 1
    stats = eng.latency_stats()
    assert stats["retries"] >= 1
    assert stats["recoveries"] == 1
    assert stats["nan_quarantined"] == 3
    assert stats["failed_requests"] == 1
    assert stats["integrity_repairs"] == 1
    assert eng.scfg.impl == impl               # no demotion occurred
