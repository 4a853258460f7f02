"""Open-loop serving of a language model through ``ServingEngine``.

The benchmark drives only the engine's request API: ``submit`` when a
request is due, ``scheduler_step`` while work remains, ``cancel`` during
warm-up, and each handle's ``state`` / ``tokens_so_far`` / ``result``.  It
stamps every request on the host clock itself: due (its scheduled arrival)
and each token, at the end of the step that made it.

A run: weights from the seed -> engine (kneading) -> warm-up of every
prefill and decode shape the mix can produce -> ramp of the mix -> window
-> tail (the mix keeps arriving until every window request has finished)
-> memory peak -> engine freed -> reference check on a sample of window
requests.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from .. import trace as tr
from .. import traffic, weights
from ..common import Clock, log, memory_peak_bytes, percentile, span

TRACE_SPAN = "traced_window"


@dataclasses.dataclass
class Tracked:
    req: traffic.LMRequest
    handle: Any
    submit_s: float
    first_s: float = math.inf
    last_s: float = math.inf
    n: int = 0
    end: str = ""
    gaps_s: List[float] = dataclasses.field(default_factory=list)
    gap_at_s: List[float] = dataclasses.field(default_factory=list)


def model_config(model: Dict[str, Any]):
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in model.items() if k in fields})


def float_weights(cfg: Dict[str, Any], seed: int):
    import jax

    from repro.models.lm import LanguageModel

    shapes = jax.eval_shape(LanguageModel(model_config(cfg["model"])).init,
                            jax.random.PRNGKey(0))
    return weights.make(shapes, weights.lm_rule(cfg["init"]), seed)


def serving_settings(cfg: Dict[str, Any], mix: Dict[str, Any]):
    s = {**cfg.get("serving", {}), **mix.get("serving", {})}
    s.setdefault("max_len", traffic.ceil_to(traffic.max_total_tokens(mix),
                                            s["kv_block"]))
    return s


def build_engine(cfg, mix, params):
    from repro.inference.engine import ServingConfig, ServingEngine

    s = serving_settings(cfg, mix)
    scfg = ServingConfig(
        impl=s["impl"], quant_bits=s.get("quant_bits", 8),
        scheduler="continuous", max_inflight=s["max_inflight"],
        max_len=s["max_len"], kv_block=s["kv_block"],
        buckets=tuple(s["buckets"]), prefill_chunk=s.get("prefill_chunk", 0))
    return ServingEngine(model_config(cfg["model"]), params, scfg)


# ------------------------------------------------------------ warm-up

def _slot_buckets(s) -> List[int]:
    return sorted({b for b in s["buckets"] if b < s["max_inflight"]}
                  | {s["max_inflight"]})


def warm_up(engine, cfg, mix) -> int:
    """Run every program the mix can make the scheduler run, through the
    public API, so the window compiles nothing.  First each admission group
    size of each prompt length (the prefill shapes, the stacking of a group,
    the copy of each new row into the cache).  Then, at each KV extent the
    mix reaches, every change of slot bucket, up and down, as the window
    meets it: the slot table filled to one bucket, then a group admitted or
    requests cancelled so that one step moves it to the other (the cache's
    pad or slice between the two batch shapes, and the decode step at
    both).  Returns the number of scheduler steps taken."""
    s = serving_settings(cfg, mix)
    prompts = sorted(mix["prompt_tokens"]["values"])
    outs = sorted(mix["output_tokens"]["values"])
    blk, chunk, cap = s["kv_block"], s.get("prefill_chunk", 0), max(
        s["buckets"])
    vocab = cfg["model"]["vocab_size"]
    rng = np.random.default_rng(0)
    toks = lambda p: rng.integers(0, vocab, p, dtype=np.int32)
    ext = lambda p, o: min(traffic.ceil_to(p + o, blk), s["max_len"])
    group = lambda p: min(cap, max(1, chunk // p)) if chunk else cap
    steps = 0

    def step():
        nonlocal steps
        engine.scheduler_step()
        steps += 1

    def drop(hs):
        for h in hs:
            h.cancel()

    for p in prompts:
        for g in range(1, group(p) + 1):
            hs = [engine.submit(toks(p), outs[-1]) for _ in range(g)]
            step()
            drop(hs)
    buckets = _slot_buckets(s)
    below = {b: ([0] + buckets)[i] for i, b in enumerate(buckets)}
    p_f = prompts[0]
    for e in sorted({ext(p, o) for p in prompts for o in outs}):
        p_a, o_a = min((p, o) for p in prompts for o in outs
                       if ext(p, o) == e)
        for b1 in buckets:
            for b2 in buckets:
                if b2 == b1:
                    continue
                live = [engine.submit(toks(p_a), o_a)]
                step()
                while len(live) < b1:
                    n = min(b1 - len(live), group(p_f))
                    live += [engine.submit(toks(p_f), outs[0])
                             for _ in range(n)]
                    step()
                if b2 > b1:
                    live += [engine.submit(toks(p_f), outs[0])
                             for _ in range(below[b2] + 1 - b1)]
                else:
                    drop(live[b2:])
                    live = live[:b2]
                step()
                drop(live)
    return steps


# ------------------------------------------------------------ serving

class Loop:
    """Submits requests when due and steps the engine, stamping tokens."""

    def __init__(self, engine, requests: List[traffic.LMRequest]):
        self.engine = engine
        self.requests = requests
        self.next = 0
        self.t0 = Clock.now()
        self.live: List[Tracked] = []
        self.all: List[Tracked] = []
        self.steps: List[Dict[str, Any]] = []

    def now(self) -> float:
        return Clock.now() - self.t0

    def run_until(self, stop) -> None:
        while not stop(self):
            now = self.now()
            while (self.next < len(self.requests)
                   and self.requests[self.next].due_s <= now):
                req = self.requests[self.next]
                with span("submit"):
                    h = self.engine.submit(req.prompt, req.output_tokens)
                t = Tracked(req, h, self.now())
                self.live.append(t)
                self.all.append(t)
                self.next += 1
            if self.live:
                self.step()
            else:
                nxt = (self.requests[self.next].due_s
                       if self.next < len(self.requests) else now + 0.01)
                with span("wait_for_arrival"):
                    time.sleep(max(0.0, min(nxt - now, 0.01)))

    def step(self) -> None:
        t_start = self.now()
        with span("scheduler_step"):
            self.engine.scheduler_step()
        t_end = self.now()
        admitted, contexts = [], []
        keep = []
        for t in self.live:
            state = t.handle.state
            if state == "queued":
                keep.append(t)
                continue
            n = len(t.handle.tokens_so_far()) if state in (
                "running", "done") else t.n
            if n > t.n:
                if t.n == 0:
                    t.first_s = t_end
                    admitted.append(len(t.req.prompt))
                    decoded = n - 1
                else:
                    decoded = n - t.n
                    # tokens that came in one step are 0 apart
                    t.gaps_s += [t_end - t.last_s] + [0.0] * (decoded - 1)
                    t.gap_at_s += [t_end] * decoded
                if decoded:
                    contexts.append(len(t.req.prompt) + n - 1)
                t.n, t.last_s = n, t_end
            if state in ("running",):
                keep.append(t)
            else:
                t.end = state
        self.live = keep
        self.steps.append({"t0": t_start, "t1": t_end,
                           "prefill": admitted, "contexts": contexts})


def run(ctx) -> Dict[str, Any]:
    import jax

    cfg, mix, seed, seconds = ctx.cfg, ctx.mix, ctx.seed, ctx.seconds
    vocab = cfg["model"]["vocab_size"]
    with span("weights"):
        params = float_weights(cfg, seed)
        jax.block_until_ready(params)
    with span("knead"):
        engine = build_engine(cfg, mix, params)
    del params
    log(f"setup: engine built at {Clock.now():.1f} s")
    with span("warm_up"):
        n_warm = warm_up(engine, cfg, mix)
    log(f"setup: warm-up {n_warm} steps done at {Clock.now():.1f} s")
    with span("arrivals"):
        requests = traffic.lm_requests(mix, seed, vocab, seconds)
    w0, w1 = traffic.window_bounds(mix, seconds)
    trace_s = min(float(mix.get("trace_s", seconds)), seconds)
    loop = Loop(engine, requests)
    loop.run_until(lambda lp: lp.now() >= w0)
    setup_s = Clock.now()
    compiles = ctx.counter.snapshot()
    window_reqs = [r for r in requests if r.phase == "window"]
    summary = None
    if ctx.trace:
        log_dir = ctx.scratch("trace")
        with tr.capture(log_dir):
            with span(TRACE_SPAN):
                t_tr0 = loop.now()
                loop.run_until(lambda lp: lp.now() >= w0 + trace_s)
                t_tr1 = loop.now()
        loop.run_until(lambda lp: lp.now() >= w1)
    else:
        loop.run_until(lambda lp: lp.now() >= w1)
    in_window = ctx.counter.since(compiles)
    tail_end = w1 + float(mix.get("tail_s", 0.0))

    def window_finished(lp) -> bool:
        win = [t for t in lp.all if t.req.phase == "window"]
        return len(win) == len(window_reqs) and all(t.end for t in win)

    loop.run_until(lambda lp: lp.now() >= tail_end or window_finished(lp))
    log(f"window: {len(window_reqs)} requests due in {seconds} s; "
        f"compilations inside the window {in_window}")
    peak = memory_peak_bytes(1)
    if ctx.trace:
        summary = tr.reduce(log_dir, TRACE_SPAN)

    win = [t for t in loop.all if t.req.phase == "window"]
    end_s = loop.now()
    for t in win:            # no first token by the end: beyond any limit
        t.first_s = min(t.first_s, end_s)
    failed = sum(1 for t in win if t.end not in ("", "done"))
    ttft = [(t.first_s - t.req.due_s) * 1e3 for t in win]
    ttft += [(end_s - r.due_s) * 1e3 for r in window_reqs[len(win):]]
    # a request that never finished counts as beyond any limit
    tpot = [(t.last_s - t.first_s) * 1e3 / (t.n - 1) if t.end == "done"
            else math.inf for t in win if t.end != "done" or t.n > 1]
    tpot += [math.inf] * (len(window_reqs) - len(win))
    late = [(t.submit_s - t.req.due_s) * 1e3 for t in win]
    log(f"window: {len(win)} sent, {sum(t.end == 'done' for t in win)} "
        f"finished, {failed} failed; generator lateness p50 "
        f"{percentile(late, 50):.3f} ms, max {max(late):.3f} ms")
    log(f"window: TTFT p50 {percentile(ttft, 50):.1f} ms p90 "
        f"{percentile(ttft, 90):.1f} ms; TPOT p50 "
        f"{percentile(tpot, 50):.2f} ms p90 {percentile(tpot, 90):.2f} ms "
        f"over {len(tpot)} requests")

    # every token gap of the window's requests, each followed to its end
    gaps_ms = [g * 1e3 for t in win for g in t.gaps_s]
    log(f"window: token gap p50 {percentile(gaps_ms, 50):.2f} ms p95 "
        f"{percentile(gaps_ms, 95):.2f} ms over {len(gaps_ms)} gaps")
    record = {"summary": summary, "steps": [], "queue_wait_ms": [],
              "token_gaps_ms": [], "model": cfg["model"]}
    if ctx.trace:
        # the traced stretch only: writing the trace stalls the host after it
        record["token_gaps_ms"] = [
            g * 1e3 for t in win for g, at in zip(t.gaps_s, t.gap_at_s)
            if t_tr0 <= at <= t_tr1]
        record["steps"] = [s for s in loop.steps
                           if s["t0"] >= t_tr0 and s["t1"] <= t_tr1]
        record["queue_wait_ms"] = [
            (t.handle._req.admit_t - t.handle._req.submit_t) * 1e3
            if t.n else math.inf
            for t in win if t_tr0 - w0 <= t.req.due_s - w0 < trace_s]
    done = [t for t in win if t.end == "done"]
    rng = traffic.rng_for(seed, 9)
    n_check = int(cfg["check"]["requests"])
    sample = []
    if done:
        longest = max(done, key=lambda t: t.n)
        rest = [t for t in done if t is not longest]
        pick = rng.permutation(len(rest))[: n_check - 1]
        sample = [longest] + [rest[i] for i in sorted(pick)]
    served = [(t.req.prompt, np.asarray(t.handle.result())) for t in sample]
    # free the program's state before the reference runs
    for t in loop.all:
        t.handle = None
    del engine, loop
    gc.collect()
    length = serving_settings(cfg, mix)["max_len"]
    gap = check_gap(cfg, seed, served, ctx.reference, length)
    limit = float(cfg["check"]["max_logit_gap"])
    control = {}
    if ctx.control:
        control["max_logit_gap"] = check_gap(
            cfg, seed, served, ctx.reference, length,
            act_dtype=cfg["check"]["control_activations"])
    return {
        "correct": bool(served) and gap <= limit,
        "attempted": len(window_reqs),
        "failed": failed + (len(window_reqs) - len(win)),
        "e2e": {"ttft_p90_ms": percentile(ttft, 90),
                "tpot_p90_ms": percentile(tpot, 90),
                "setup_s": setup_s},
        "checks": {"max_logit_gap": (gap, limit)},
        "memory_peak_bytes": peak,
        "record": record,
        "compiles_in_window": in_window,
        "control": control,
    }


def check_gap(cfg, seed, served, ref, length: int,
              act_dtype="float32") -> float:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample.  With
    ``act_dtype`` narrower than float32 the tokens are the ones that a
    forward in that precision ranks first at each position instead."""
    import jax.numpy as jnp

    if not served:
        return math.inf
    w = ref.serving_weights(float_weights(cfg, seed))
    model = dict(cfg["model"])
    gap = 0.0
    for prompt, out in served:
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        pad = np.zeros(length - len(seq), np.int32)
        ids = jnp.asarray(np.concatenate([seq, pad]))
        lg = np.asarray(ref.logits(w, ids, model), np.float64)
        rows = lg[len(prompt) - 1: len(prompt) - 1 + len(out)]
        toks = out
        if act_dtype != "float32":
            low = np.asarray(ref.logits(w, ids, model, act_dtype))
            toks = low[len(prompt) - 1: len(prompt) - 1 + len(out)].argmax(-1)
        picked = rows[np.arange(len(out)), toks]
        gap = max(gap, float((rows.max(-1) - picked).max()))
    return gap

