"""Per-arch smoke tests (reduced configs, one fwd/train step on CPU) +
attention/SSM equivalence properties + decode==full-forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import layers, ssm
from repro.models.lm import LanguageModel

B, S = 2, 32


def _batch(cfg, key, seq=S):
    b = {"tokens": jax.random.randint(key, (B, seq), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (B, seq), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        b["image_embeds"] = jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_trainstep(arch):
    """Instantiate the reduced config, run forward + one SGD step: shapes
    correct, loss finite, gradients finite and nonzero."""
    cfg = get_config(arch, smoke=True)
    model = LanguageModel(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = _batch(cfg, key)

    logits = model.logits(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))

    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0

    # one SGD step reduces nothing catastrophic (loss stays finite)
    params2 = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                           params, grads)
    loss2 = model.loss(params2, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_matches_full_forward(arch):
    """prefill(S) + decode_step(S) logits == full forward at position S."""
    cfg = get_config(arch, smoke=True)
    model = LanguageModel(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    batch = _batch(cfg, key, seq=S + 1)
    full = model.logits(params, batch)

    pre = dict(batch, tokens=batch["tokens"][:, :S])
    pre.pop("labels")
    _, cache = model.prefill(params, pre)

    def pad(x):
        if x.ndim >= 4 and x.shape[-3] == S:
            pads = [(0, 0)] * x.ndim
            pads[-3] = (0, 16)
            return jnp.pad(x, pads)
        return x
    cache = jax.tree.map(pad, cache)
    tok = batch["tokens"][:, S:S + 1]
    pos = jnp.full((B,), S, jnp.int32)
    dec, _ = model.decode_step(params, tok, pos, cache)
    err = jnp.max(jnp.abs(dec.astype(jnp.float32)
                          - full[:, -1].astype(jnp.float32)))
    scale = jnp.max(jnp.abs(full[:, -1].astype(jnp.float32))) + 1e-6
    assert float(err / scale) < 0.05    # bf16 accumulation tolerance


@pytest.mark.parametrize("kv_bits", [0, 8], ids=["bf16", "int8"])
def test_decode_writes_only_each_rows_new_entry(kv_bits):
    """One decode step over rows at mixed positions (0, max_len - 1, and
    free rows the scheduler pads with token 0 at position 0) on a cache
    holding junk from each row's position on: live rows' logits equal the
    full forward at their position, each row's entry at pos[row] becomes
    its new K/V, and every other cache entry is bit-unchanged."""
    cfg = dataclasses.replace(get_config("smollm-360m", smoke=True),
                              kv_cache_bits=kv_bits)
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(2))
    b, max_len = 8, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, max_len), 0,
                              cfg.vocab_size)
    full = model.logits(params, {"tokens": toks})
    _, filled = model.prefill(params, {"tokens": toks})
    pos = jnp.array([0, 1, 5, max_len - 1, 9, 0, 0, 3], jnp.int32)
    live = np.array([1, 1, 1, 1, 1, 0, 0, 1], bool)
    tok = jnp.where(live[:, None],
                    jnp.take_along_axis(toks, pos[:, None], axis=1), 0)

    kpos = np.arange(max_len)
    at = kpos[None, :] == np.asarray(pos)[:, None]                # [B, S]
    cache = {}
    for i, (name, c) in enumerate(filled.items()):
        later = (kpos[None, :] >= np.asarray(pos)[:, None]).reshape(
            (1, b, max_len) + (1,) * (c.ndim - 3))
        junk = jax.random.normal(jax.random.PRNGKey(10 + i), c.shape)
        if c.dtype == jnp.int8:
            junk = jnp.clip(junk * 50, -127, 127)
        cache[name] = jnp.where(later, junk.astype(c.dtype), c)
    before = {name: np.asarray(c) for name, c in cache.items()}

    logits, out = jax.jit(model.decode_step, donate_argnums=(3,))(
        params, tok, pos, cache)
    assert set(out) == set(before)
    for name, c in out.items():
        c = np.asarray(c)
        mask = at.reshape((1, b, max_len) + (1,) * (c.ndim - 3))
        np.testing.assert_array_equal(np.where(mask, 0, c),
                                      np.where(mask, 0, before[name]))

    rows = np.flatnonzero(live)
    ref = full[rows, pos[rows]].astype(jnp.float32)
    err = jnp.max(jnp.abs(logits[rows].astype(jnp.float32) - ref))
    assert float(err / jnp.max(jnp.abs(ref))) < (0.05 if kv_bits == 0
                                                 else 0.1)
    entry = lambda c: np.asarray(c)[:, rows, np.asarray(pos)[rows]]
    if kv_bits == 0:
        for name in ("k", "v"):
            np.testing.assert_array_equal(entry(out[name]),
                                          entry(filled[name]))
    else:
        for name in ("k", "v"):
            deq = lambda c: (entry(c[name]).astype(np.float32)
                             * entry(c[name + "_scale"])[..., None])
            want = deq(filled)
            np.testing.assert_allclose(deq(out), want,
                                       atol=0.02 * np.abs(want).max())


# ----------------------------------------------------------- attention eqv
def test_flash_equals_full_attention_and_grads():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 128, 2, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 2, 16))
    ref = layers.full_attention(q, k, v, causal=True)
    out = layers.flash_attention(q, k, v, True, 32, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    g1 = jax.grad(lambda *a: jnp.sum(
        jnp.tanh(layers.full_attention(*a, causal=True))), (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(
        jnp.tanh(layers.flash_attention(*a, True, 32, 0))), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_masked_equals_flash_forward():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 256, 1, 3, 32))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 1, 32))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 1, 32))
    a = layers.chunked_attention(q, k, v, causal=True, chunk=64, exact=False)
    b = layers.flash_attention(q, k, v, True, 64, 0)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=1e-5)


# ------------------------------------------------------------------ SSD eqv
def test_ssd_chunked_equals_stepwise():
    key = jax.random.PRNGKey(0)
    Bz, L, H, p, n = 2, 48, 2, 8, 4
    ks = jax.random.split(key, 4)
    u = jax.random.normal(ks[0], (Bz, L, H, p))
    b = jax.random.normal(ks[1], (Bz, L, H, n))
    c = jax.random.normal(ks[2], (Bz, L, H, n))
    log_a = -jax.nn.softplus(jax.random.normal(ks[3], (Bz, L, H)))
    y_c, h_c = ssm.ssd_chunked(u, b, c, log_a, chunk=16)
    h = jnp.zeros((Bz, H, p, n))
    ys = []
    for t in range(L):
        y, h = ssm.ssd_step(u[:, t], b[:, t], c[:, t], log_a[:, t], h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_c), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_c), atol=1e-4)


def test_causal_conv_streaming_equals_batch():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 20, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6)) * 0.3
    y_full, _ = ssm.causal_conv(x, w)
    state = None
    outs = []
    for t in range(20):
        y, state = ssm.causal_conv(x[:, t:t + 1], w, state)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y_full), atol=1e-5)
