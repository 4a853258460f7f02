"""Closed-loop image classification through ``CNNServingEngine``.

Each client submits one image, drains, and sends the next when its reply
is back (``clients`` = 1, batch 1 in the stream mix).  Images are drawn
from the seed on the device, a fixed pool cycled in a seeded order.  The
benchmark stamps each step (submit -> drain) on the host clock.

A run: weights from the seed -> engine (kneading) -> one warm image ->
ramp -> window -> memory peak -> engine freed -> reference check on a
sample of the window's images.
"""
from __future__ import annotations

import gc
from typing import Any, Dict

import numpy as np

from .. import trace as tr
from .. import traffic, weights
from ..common import Clock, log, memory_peak_bytes, span

TRACE_SPAN = "traced_window"


def cnn_config(model: Dict[str, Any]):
    from repro.models import cnn

    return cnn.CNNConfig(
        name=model["name"], spec=tuple(tuple(s) for s in model["spec"]),
        in_channels=model["in_channels"], image_size=model["image_size"],
        num_classes=model["num_classes"])


def float_weights(cfg: Dict[str, Any], seed: int):
    import jax

    from repro.models import cnn

    ccfg = cnn_config(cfg["model"])
    shapes = jax.eval_shape(lambda k: cnn.init(k, ccfg),
                            jax.random.PRNGKey(0))
    return weights.make(shapes, weights.cnn_rule(cfg["init"]), seed)


def images_for(cfg, mix, seed):
    import jax

    m = cfg["model"]
    shape = (int(mix["images"]), m["image_size"], m["image_size"],
             m["in_channels"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.jit(lambda k: jax.random.normal(k, shape))(key)


def run(ctx) -> Dict[str, Any]:
    import jax

    from repro.inference.cnn_engine import CNNServingConfig, CNNServingEngine

    cfg, mix, seed, seconds = ctx.cfg, ctx.mix, ctx.seed, ctx.seconds
    batch = int(mix.get("batch", 1))
    if mix["arrival"]["kind"] != "closed" or mix["arrival"]["clients"] != 1:
        raise ValueError("cnn_stream serves one closed-loop client")
    s = {**cfg.get("serving", {}), **mix.get("serving", {})}
    with span("weights"):
        params = float_weights(cfg, seed)
        images = images_for(cfg, mix, seed)
        jax.block_until_ready((params, images))
    with span("knead"):
        engine = CNNServingEngine(cnn_config(cfg["model"]), params,
                                  CNNServingConfig(
                                      impl=s["impl"], bits=s.get("bits", 8),
                                      jit=True, buckets=(batch,),
                                      keep_float_params=False))
    del params
    n_img = images.shape[0]
    order = traffic.rng_for(seed, 3).permutation(n_img)
    served: Dict[int, Any] = {}
    steps = []

    def one(i: int, keep: bool):
        idx = [int(order[(i * batch + j) % n_img]) for j in range(batch)]
        t0 = Clock.now()
        with span("submit"):
            hs = [engine.submit(images[k]) for k in idx]
        with span("drain"):
            engine.drain()
        out = [h.result() for h in hs]
        jax.block_until_ready(out)
        t1 = Clock.now()
        if keep:
            for k, o in zip(idx, out):
                served.setdefault(k, o)
        steps.append((t0, t1))

    with span("warm_up"):
        one(0, False)
    log(f"setup: engine built and warm at {Clock.now():.1f} s")
    i = 1
    ramp_end = Clock.now() + float(mix.get("ramp_s", 0.0))
    while Clock.now() < ramp_end:
        one(i, False)
        i += 1
    setup_s = Clock.now()
    w0, w1 = setup_s, setup_s + seconds
    first = len(steps)
    compiles = ctx.counter.snapshot()
    summary, t_tr = None, None
    trace_s = min(float(mix.get("trace_s", seconds)), seconds)

    def serve_until(t_stop):
        nonlocal i
        while Clock.now() < t_stop:
            one(i, True)
            i += 1

    if ctx.trace:
        log_dir = ctx.scratch("trace")
        with tr.capture(log_dir):
            with span(TRACE_SPAN):
                t_tr0 = Clock.now()
                serve_until(w0 + trace_s)
                t_tr = (t_tr0, Clock.now())
        serve_until(w1)
    else:
        serve_until(w1)
    in_window = ctx.counter.since(compiles)
    win = [(a, b) for a, b in steps[first:] if b <= w1]
    images_s = len(win) * batch / sum(b - a for a, b in win)
    log(f"window: {len(win) * batch} images in {seconds} s, "
        f"{images_s:.3f} images/s; compilations inside the window "
        f"{in_window}")
    peak = memory_peak_bytes(1)
    record = {"summary": None, "images": 0, "model": cfg["model"]}
    if ctx.trace:
        record["summary"] = tr.reduce(log_dir, TRACE_SPAN)
        record["images"] = batch * sum(
            1 for a, b in steps[first:] if a >= t_tr[0] and b <= t_tr[1])

    rng = traffic.rng_for(seed, 9)
    keys = sorted(served)
    pick = sorted(rng.permutation(len(keys))[: int(cfg["check"]["images"])])
    sample = [keys[j] for j in pick]
    got = np.stack([np.asarray(served[k]) for k in sample])
    imgs = np.asarray(images)[sample]
    del engine, served, images
    gc.collect()
    err = check_err(cfg, seed, imgs, got, ctx.reference)
    limit = float(cfg["check"]["max_rel_err"])
    control = {}
    if ctx.control:
        control["max_rel_err"] = check_err(cfg, seed, imgs, got,
                                           ctx.reference, passes="high3")
    return {
        "correct": bool(len(sample)) and err <= limit,
        "attempted": len(win) * batch,
        "failed": 0,
        "e2e": {"images_s": images_s, "setup_s": setup_s},
        "checks": {"max_rel_err": (err, limit)},
        "memory_peak_bytes": peak,
        "record": record,
        "compiles_in_window": in_window,
        "control": control,
    }


def rel_errs(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per image: the largest logit error over the largest reference logit."""
    got = got.astype(np.float64)
    ref = ref.astype(np.float64)
    return np.abs(got - ref).max(-1) / np.abs(ref).max(-1)


def check_err(cfg, seed, images, got, ref, passes="highest") -> float:
    """Worst per-image relative logit error of ``got`` against the float32
    reference on the same 8-bit weights.  With ``passes="high3"`` the
    reference itself in three bf16 passes stands in for ``got``."""
    w = ref.serving_weights(float_weights(cfg, seed))
    want = np.stack([np.asarray(ref.logits(w, images[i:i + 1], cfg["model"]))
                     [0] for i in range(len(images))])
    if passes != "highest":
        got = np.stack([np.asarray(ref.logits(w, images[i:i + 1],
                                              cfg["model"], passes))[0]
                        for i in range(len(images))])
    return float(rel_errs(got, want).max())
