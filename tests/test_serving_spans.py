"""The serving engines' own spans in a profiler trace (``serve.*``).

A trace captured on the CPU around a few steps of a tiny continuous
``ServingEngine``, and around tiny ``CNNServingEngine`` drains, must hold
the spans under their contract names, nested as the readers expect, with
counters that match the scheduler's own state, and with at most one wait
on the device per kind in a step.  Also the public request stamps.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs.registry import get_config
from repro.inference.cnn_engine import CNNServingConfig, CNNServingEngine
from repro.inference.engine import ServingConfig, ServingEngine
from repro.inference.resilience import ServingFaultPolicy
from repro.models import cnn
from repro.models.lm import LanguageModel

WINDOW = "test_window"
LM_SPANS = {"serve.step", "serve.admit", "serve.prefill",
            "serve.prefill_sync", "serve.cache_write", "serve.decode",
            "serve.token_sync", "serve.retire"}
CNN_SPANS = {"serve.batch", "serve.forward", "serve.forward_sync",
             "serve.finish"}


class Span:
    def __init__(self, e):
        self.name = e.name
        self.start = int(e.start_ns)
        self.end = int(e.start_ns + e.duration_ns)
        self.stats = dict(e.stats)

    def holds(self, other) -> bool:
        return self.start <= other.start and other.end <= self.end


def _capture(tmp_path, body):
    """Run ``body`` under a profiler trace; the ``serve.*`` spans of the
    host line that holds the window span, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation(WINDOW):
            body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            if any(e.name == WINDOW for e in events):
                spans = [Span(e) for e in events
                         if e.name.startswith("serve.")]
                return sorted(spans, key=lambda s: (s.start, -s.end))
    raise AssertionError("no host line holds the window span")


def _inside(spans, outer_name, inner):
    return [s for s in spans if s.name == outer_name and s.holds(inner)]


@pytest.fixture(scope="module")
def lm_trace(tmp_path_factory):
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServingConfig(
        max_len=48, impl="planes", knead_min_dim=8, buckets=(1, 2, 4),
        scheduler="continuous", max_inflight=3, kv_block=16))
    sched = eng._scheduler
    decodes, groups = [], []
    decode_once, admit_group = sched._decode_once, sched._admit_group

    def record_decode():
        live = [i for i, r in enumerate(sched.slots) if r is not None]
        if live:
            decodes.append((len(live), sched._batch, sched._extent))
        decode_once()

    def record_admit(group, plen, bucket):
        groups.append(([r.id for r in group], bucket, plen))
        admit_group(group, plen, bucket)

    sched._decode_once = record_decode
    sched._admit_group = record_admit
    # two groups of one length, a lone longer prompt, a one-token request
    # and a late arrival that reuses a shape
    spec = [(6, 5), (6, 3), (9, 4), (4, 1), (9, 2)]
    handles = []

    def serve():
        for i, (plen, n) in enumerate(spec[:4]):
            toks = jax.random.randint(jax.random.PRNGKey(50 + i), (plen,), 0,
                                      cfg.vocab_size)
            handles.append(eng.submit(toks, n))
        for _ in range(3):
            eng.scheduler_step()
        plen, n = spec[4]
        handles.append(eng.submit(jnp.arange(plen, dtype=jnp.int32), n))
        while eng.scheduler_step():
            pass

    spans = _capture(tmp_path_factory.mktemp("lm"), serve)
    return eng, spans, decodes, groups, handles


def test_lm_span_names_and_nesting(lm_trace):
    _, spans, _, _, _ = lm_trace
    assert {s.name for s in spans} == LM_SPANS
    steps = [s for s in spans if s.name == "serve.step"]
    for s in spans:
        if s.name != "serve.step":
            assert len(_inside(spans, "serve.step", s)) == 1, s.name
    for s in spans:
        if s.name in ("serve.prefill", "serve.prefill_sync",
                      "serve.cache_write"):
            assert _inside(spans, "serve.admit", s), s.name
    for step in steps:
        inner = [s for s in spans if step.holds(s) and s is not step]
        names = [s.name for s in inner]
        # each wait on the device once at most, and no sync nested in work
        assert names.count("serve.prefill_sync") <= 1
        assert names.count("serve.token_sync") <= 1
        order = [n for n in names if n not in ("serve.admit",)]
        # admit (prefill, its sync, the cache write), then decode, its
        # sync and the retirements, one after the other
        assert order == [n for n in ("serve.prefill", "serve.prefill_sync",
                                     "serve.cache_write", "serve.decode",
                                     "serve.token_sync", "serve.retire")
                         if n in names]
        for a, b in zip(inner, inner[1:]):
            assert a.end <= b.start or a.holds(b)


def test_lm_decode_counters_match_the_slot_table(lm_trace):
    _, spans, decodes, _, _ = lm_trace
    got = [(s.stats["live"], s.stats["rows"], s.stats["extent"])
           for s in spans if s.name == "serve.decode"]
    assert got == decodes and len(got) >= 4
    # the slot bucket changed between steps, and each span tells which
    assert len({s.stats["rows"] for s in spans
                if s.name == "serve.decode"}) >= 2


def test_lm_admission_counters_list_the_group(lm_trace):
    _, spans, _, groups, handles = lm_trace
    admits = [s for s in spans if s.name == "serve.admit"]
    assert len(admits) == len(groups) >= 3
    for adm, (ids, bucket, plen) in zip(admits, groups):
        assert (adm.stats["n"], adm.stats["bucket"], adm.stats["plen"]) == \
            (len(ids), bucket, plen)
    assert sorted(i for ids, _, _ in groups for i in ids) == \
        sorted(int(h) for h in handles)


def test_lm_spans_without_readers_carry_no_counters(lm_trace):
    """Only ``serve.admit`` and ``serve.decode`` carry counters, each an
    int the scheduler already holds; one cache write per admission and one
    retirement per decode."""
    _, spans, _, groups, _ = lm_trace
    for s in spans:
        want = {"serve.admit": {"n", "bucket", "plen"},
                "serve.decode": {"live", "rows", "extent"}}.get(s.name, set())
        assert set(s.stats) == want, s.name
    names = [s.name for s in spans]
    assert names.count("serve.cache_write") == len(groups)
    assert names.count("serve.retire") == names.count("serve.decode")


def test_lm_request_stamps(lm_trace):
    eng, _, _, _, handles = lm_trace
    for h in handles:
        assert h.state == "done"
        assert 0 < h.submit_t <= h.admit_t < h.first_token_t <= h.finish_t
    stats = eng.latency_stats()
    assert stats["ttft_p50_ms"] <= stats["ttft_p95_ms"] <= stats["max_ms"]


def test_fault_policy_waits_are_sync_spans(tmp_path):
    """With a fault policy the watchdog and the NaN guard wait on the
    device too: each wait sits in a ``*_sync`` span of its own."""
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServingConfig(
        max_len=48, impl="float", buckets=(1, 2), scheduler="continuous",
        max_inflight=2, kv_block=16, fault_policy=ServingFaultPolicy()))

    def serve():
        for plen, n in ((6, 3), (5, 2)):
            eng.submit(jnp.arange(plen, dtype=jnp.int32), n)
        while eng.scheduler_step():
            pass

    spans = _capture(tmp_path, serve)
    decodes = [s for s in spans if s.name == "serve.decode"]
    assert decodes
    for dec in decodes:
        inner = [s.name for s in spans if dec.holds(s) and s is not dec]
        assert inner == ["serve.watchdog_sync", "serve.guard_sync"]
    for pre in (s for s in spans if s.name == "serve.prefill"):
        assert [s.name for s in spans if pre.holds(s) and s is not pre] == \
            ["serve.guard_sync"]


def test_cnn_drain_spans(tmp_path):
    cfg = dataclasses.replace(cnn.CNN_ZOO["nin"], image_size=16)
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    eng = CNNServingEngine(cfg, params, CNNServingConfig(
        impl="planes", buckets=(1, 2, 4), jit=False))
    img = jax.random.normal(jax.random.PRNGKey(1), (16, 16, 3))

    def serve():
        for n in (3, 1, 4):
            for _ in range(n):
                eng.submit(img)
            eng.drain()

    spans = _capture(tmp_path, serve)
    assert {s.name for s in spans} == CNN_SPANS
    batches = [s for s in spans if s.name == "serve.batch"]
    assert [(s.stats["n"], s.stats["bucket"]) for s in batches] == \
        [(3, 4), (1, 1), (4, 4)]
    assert all(not s.stats for s in spans if s.name != "serve.batch")
    names = [s.name for s in spans]
    assert names == ["serve.batch", "serve.forward", "serve.forward_sync",
                     "serve.finish"] * 3
    for a, b in zip(spans, spans[1:]):
        assert a.end <= b.start
