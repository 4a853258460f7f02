#!/usr/bin/env python3
"""Chip smoke run: kneaded serving end to end on a TPU, kernel compiled.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # mesh-sharded serving vs one device

One chip.  smollm-360m at its published config (32 layers, seeded random
weights, nothing downloaded) is served through ``ServingEngine`` with
``impl="pallas"`` and the continuous scheduler: 8 requests with prompts of
32-256 tokens, 16 new tokens each.  VGG-16 (``CNN_ZOO["vgg16"]``) is served
through ``CNNServingEngine(impl="pallas")``: 8 images.  Checks:

  * every request finishes, LM tokens in the vocabulary, CNN logits finite;
  * the prefill logits of two prompts, and every image's logits, agree
    with a float32 reference run on the dequantized kneaded weights under
    ``jax.default_matmul_precision("highest")`` (same math, see tolerances);
  * one full-width projection through the SAC kernel agrees with a float64
    product of the same weights (the kernel's own f32 precision);
  * the engine still serves ``impl="pallas"`` (no fault policy, so no
    demotion) and the compiled decode step holds the Mosaic kernel.

``--chips 4`` runs only the sharded path: the same requests through
``shards=4`` engines on a four-device "model" mesh, compared in the same
process with ``shards=0`` on one device — identical tokens, logits within
tolerance, and every N-sharded weight spread over four distinct devices.

Times printed are smoke figures from one run with compilation included
(warm only as far as the persistent compile cache it reports), not
benchmarks.  The last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script exits
non-zero, printing no result, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
LM_ARCH = "smollm-360m"
PROMPT_LENS = (32, 32, 96, 96, 160, 160, 256, 256)
NEW_TOKENS = 16
MAX_INFLIGHT = 8
CNN_ARCH = "vgg16"
N_IMAGES = 8

# Tolerances, as max |got - ref| / max |ref| over a logits row.
# LM: the engine serves bf16 activations (cfg.dtype), the reference is f32
# end to end; at these widths and 32 layers the bf16 path lands at
# 0.014-0.015 on XLA-CPU (int path vs the same reference), so 0.05 leaves
# 3x headroom while a lost bit plane or a wrong tile moves logits by O(1).
LM_LOGIT_TOL = 5e-2
# CNN: f32 activations end to end on both sides; only the f32 summation
# order differs (~1e-6).  A bf16 pass over the activations would show ~4e-3.
CNN_LOGIT_TOL = 1e-4
# One projection, f32 activations, against a float64 product.
KERNEL_TOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def tpu_device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX's default device is "
                 f"{dev.platform!r}; this script runs on the chip only")
    return dev


# ----------------------------------------------------------------- LM

def lm_setup():
    import jax

    from repro.configs.registry import get_config
    from repro.models.lm import LanguageModel

    cfg = get_config(LM_ARCH, smoke=False)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in PROMPT_LENS]
    return cfg, params, prompts


def lm_engine(cfg, params, shards: int = 0):
    from repro.inference.engine import ServingConfig, ServingEngine

    return ServingEngine(cfg, params, ServingConfig(
        impl="pallas", scheduler="continuous", max_inflight=MAX_INFLIGHT,
        shards=shards))


def lm_serve(eng, prompts, tag: str):
    """Submit every prompt, drain, check each request; returns tokens."""
    import jax.numpy as jnp

    from repro.inference import frontend as fe

    handles = [eng.submit(jnp.asarray(p), NEW_TOKENS) for p in prompts]
    eng.drain()
    vocab = eng.cfg.vocab_size
    outs = []
    for i, (h, p) in enumerate(zip(handles, prompts)):
        check(h.state == fe.DONE, f"{tag} request {i} ended {h.state}")
        toks = np.asarray(h.result())
        ok = toks.shape == (NEW_TOKENS,) and bool(
            ((toks >= 0) & (toks < vocab)).all())
        log(f"{tag} request {i}: prompt {len(p)} tokens -> "
            f"{toks.shape[0]} new tokens, in vocab: {ok}")
        check(ok, f"{tag} request {i} tokens {toks.tolist()}")
        outs.append(toks)
    check(eng.scfg.impl == "pallas",
          f"{tag} engine left the pallas path: impl={eng.scfg.impl}")
    return outs


def prefill_logits(eng, prompt):
    import jax.numpy as jnp

    with eng._mesh_ctx():
        logits, _ = eng._prefill(eng.params,
                                 {"tokens": jnp.asarray(prompt)[None]})
    return np.asarray(logits.astype(jnp.float32))[0]


def reference_prefill_logits(eng, prompt):
    """f32 reference: the same kneaded params, dequantized (impl="float"
    is ``a @ unknead(kw)``), every activation in float32."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import LanguageModel

    ref = LanguageModel(dataclasses.replace(eng.cfg, impl="float",
                                            dtype="float32"))
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(ref.prefill)(
            eng.params, {"tokens": jnp.asarray(prompt)[None]})
    return np.asarray(logits)[0]


def decode_holds_kernel(eng) -> bool:
    """Compile the decode step at the shape the scheduler served with all
    slots live and look for the Mosaic kernel in the compiled program."""
    import jax
    import jax.numpy as jnp

    blk = eng.scfg.kv_block
    extent = -(-(max(PROMPT_LENS) + NEW_TOKENS) // blk) * blk
    cache = eng.model.cache_spec(batch=MAX_INFLIGHT, max_len=extent)
    text = eng._decode.lower(
        eng.params, jax.ShapeDtypeStruct((MAX_INFLIGHT, 1), jnp.int32),
        jax.ShapeDtypeStruct((MAX_INFLIGHT,), jnp.int32), cache
    ).compile().as_text()
    return "tpu_custom_call" in text


def kernel_precision(eng) -> float:
    """Layer 0's widest kneaded projection through the SAC kernel with f32
    activations, against a float64 product of the dequantized weight."""
    import jax

    from repro.core.kneading import KneadedWeight, unknead
    from repro.kernels.sac_matmul.ops import sac_matmul_pallas

    leaves = jax.tree.leaves(eng.params,
                             is_leaf=lambda x: isinstance(x, KneadedWeight))
    stacked = max((x for x in leaves if isinstance(x, KneadedWeight)),
                  key=lambda x: x.k * x.n)
    kw = jax.tree.map(lambda x: x[0], stacked)
    a = np.random.default_rng(SEED + 2).normal(
        size=(8, kw.k)).astype(np.float32)
    got = np.asarray(sac_matmul_pallas(a, kw))
    ref = a.astype(np.float64) @ np.asarray(unknead(kw), np.float64)
    err = rel_err(got, ref)
    log(f"lm kernel: layer-0 projection [{kw.k} x {kw.n}], f32 activations,"
        f" rel err vs float64 {err:.3e} (tol {KERNEL_TOL:.0e})")
    return err


def phase_lm(cfg, params, prompts):
    from repro.inference.engine import serving_bytes

    t0 = time.perf_counter()
    eng = lm_engine(cfg, params)
    t_knead = time.perf_counter() - t0
    log(f"lm: {cfg.name} {cfg.num_layers} layers d_model {cfg.d_model} "
        f"d_ff {cfg.d_ff} vocab {cfg.vocab_size}; serving bytes "
        f"{serving_bytes(eng.params)} (kneaded int8, impl=pallas)")
    t1 = time.perf_counter()
    lm_serve(eng, prompts, "lm")
    t_serve = time.perf_counter() - t1
    log(f"lm smoke figure (one run, compilation included, not a "
        f"benchmark): knead {t_knead:.1f} s, serve {len(prompts)} x "
        f"{NEW_TOKENS} tokens {t_serve:.1f} s")
    for i in (0, len(prompts) - 1):
        err = rel_err(prefill_logits(eng, prompts[i]),
                      reference_prefill_logits(eng, prompts[i]))
        log(f"lm prefill logits, prompt {len(prompts[i])} tokens: rel err "
            f"vs f32 reference {err:.3e} (tol {LM_LOGIT_TOL:.0e})")
        check(err <= LM_LOGIT_TOL, f"lm prefill logits err {err}")
    check(kernel_precision(eng) <= KERNEL_TOL, "kernel f32 precision")
    held = decode_holds_kernel(eng)
    log(f"lm compiled decode step holds the SAC kernel: {held}")
    check(held, "no tpu_custom_call in the compiled decode step")


# ---------------------------------------------------------------- CNN

def cnn_setup():
    import jax

    from repro.models import cnn

    cfg = cnn.CNN_ZOO[CNN_ARCH]
    params = cnn.init(jax.random.PRNGKey(SEED + 1), cfg)
    images = jax.random.normal(
        jax.random.PRNGKey(SEED + 3),
        (N_IMAGES, cfg.image_size, cfg.image_size, cfg.in_channels))
    return cfg, params, images


def cnn_engine(cfg, params, shards: int = 0):
    from repro.inference.cnn_engine import CNNServingConfig, CNNServingEngine

    return CNNServingEngine(cfg, params, CNNServingConfig(
        impl="pallas", jit=True, shards=shards))


def cnn_serve(eng, images):
    handles = [eng.submit(images[i]) for i in range(images.shape[0])]
    eng.drain()
    return np.stack([np.asarray(h.result()) for h in handles])


def cnn_reference(eng, images):
    """f32 reference forward on the dequantized kneaded filters."""
    import jax

    from repro.core.kneading import unknead
    from repro.models import cnn

    ref_params = {name: {"w": unknead(p["w"])[:p["w"].logical_k,
                                              :p["w"].logical_n],
                         "b": p["b"]}
                  for name, p in eng.params.items()}
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: cnn.apply(p, x, eng.cfg, impl="float"))(
            ref_params, images)
    return np.asarray(out)


def phase_cnn(cfg, params, images):
    t0 = time.perf_counter()
    eng = cnn_engine(cfg, params)
    t_knead = time.perf_counter() - t0
    log(f"cnn: {cfg.name} {cfg.image_size}px, serving bytes "
        f"{eng.serving_bytes()} (kneaded int8, impl=pallas)")
    t1 = time.perf_counter()
    got = cnn_serve(eng, images)
    t_serve = time.perf_counter() - t1
    log(f"cnn smoke figure (one run, compilation included, not a "
        f"benchmark): knead {t_knead:.1f} s, serve {N_IMAGES} images "
        f"{t_serve:.1f} s")
    ref = cnn_reference(eng, images)
    for i in range(N_IMAGES):
        err = rel_err(got[i], ref[i])
        ok = bool(np.isfinite(got[i]).all()) and err <= CNN_LOGIT_TOL
        log(f"cnn request {i}: logits {got[i].shape}, rel err vs f32 "
            f"reference {err:.3e} (tol {CNN_LOGIT_TOL:.0e}): {ok}")
        check(ok, f"cnn request {i} err {err}")


# ------------------------------------------------------ four chips

def assert_spread(params, n: int, tag: str) -> int:
    """Every N-sharded kneaded array must hold one distinct shard per
    device of the mesh — not every shard on device 0."""
    import jax

    from repro.core.schedule import ShardedKneadedWeight

    leaves = jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, ShardedKneadedWeight))
    sharded = [x for x in leaves if isinstance(x, ShardedKneadedWeight)]
    check(bool(sharded), f"{tag}: no sharded kneaded weights")
    for kw in sharded:
        devs = {s.device for s in kw.planes.addressable_shards}
        check(len(devs) == n and not kw.planes.sharding.is_fully_replicated,
              f"{tag}: planes {kw.planes.shape} on {len(devs)} devices, "
              f"sharding {kw.planes.sharding}")
    return len(sharded)


def phase_lm_sharded(cfg, params, prompts, n: int):
    t0 = time.perf_counter()
    one = lm_engine(cfg, params)
    ref_tokens = lm_serve(one, prompts, "lm 1-device")
    four = lm_engine(cfg, params, shards=n)
    k = assert_spread(four.params, n, "lm")
    log(f"lm: {k} kneaded weights N-sharded over {n} devices "
        f"{[str(d) for d in four.mesh.devices.flat]}")
    tokens = lm_serve(four, prompts, f"lm {n}-shard")
    for i, (a, b) in enumerate(zip(tokens, ref_tokens)):
        same = bool(np.array_equal(a, b))
        log(f"lm request {i}: {n}-shard tokens identical to 1-device: {same}")
        check(same, f"lm request {i}: {a.tolist()} vs {b.tolist()}")
    for i in (0, len(prompts) - 1):
        err = rel_err(prefill_logits(four, prompts[i]),
                      prefill_logits(one, prompts[i]))
        log(f"lm prefill logits, prompt {len(prompts[i])} tokens: {n}-shard "
            f"vs 1-device rel err {err:.3e} (tol {LM_LOGIT_TOL:.0e})")
        check(err <= LM_LOGIT_TOL, f"lm sharded prefill logits err {err}")
    log(f"lm sharded smoke figure (compilation included, not a "
        f"benchmark): {time.perf_counter() - t0:.1f} s")


def phase_cnn_sharded(cfg, params, images, n: int):
    t0 = time.perf_counter()
    ref = cnn_serve(cnn_engine(cfg, params), images)
    four = cnn_engine(cfg, params, shards=n)
    k = assert_spread(four.params, n, "cnn")
    log(f"cnn: {k} kneaded layers N-sharded over {n} devices")
    got = cnn_serve(four, images)
    for i in range(N_IMAGES):
        err = rel_err(got[i], ref[i])
        log(f"cnn request {i}: {n}-shard vs 1-device rel err {err:.3e} "
            f"(tol {CNN_LOGIT_TOL:.0e})")
        check(err <= CNN_LOGIT_TOL, f"cnn sharded request {i} err {err}")
    log(f"cnn sharded smoke figure (compilation included, not a "
        f"benchmark): {time.perf_counter() - t0:.1f} s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh-sharded comparison")
    args = ap.parse_args()

    dev = tpu_device()
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    held = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({held} entries at start)")
    count = len(jax.devices())
    log(f"device: {dev.platform} {dev.device_kind} x{count}")
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_lm(*lm_setup())
        phase_cnn(*cnn_setup())
    else:
        check(count >= args.chips,
              f"--chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {count}")
        phase_lm_sharded(*lm_setup(), n=args.chips)
        phase_cnn_sharded(*cnn_setup(), n=args.chips)
    log(f"total wall {time.perf_counter() - t0:.1f} s (smoke figure, "
        f"compilation included)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
