"""Plain float32 forward of SmolLM-360M (Llama architecture), in jax.numpy.

HuggingFaceTB/SmolLM-360M: pre-norm decoder, RMSNorm, rotary positions
(half-split rotation), grouped-query attention (15 query heads over 5
key/value heads of 64), SwiGLU MLP, output head tied to the embedding.
No kernel, cache or batching: one sequence at a time, every position.

Departures, each as the serving configuration states it:
  * every layer projection is the 8-bit weight the server multiplies by
    (``quant.fake_quant``: per-output-channel scales); the embedding and the
    tied head stay float32, as served;
  * RMSNorm epsilon is the configuration's ``norm_eps`` (the server's value,
    1e-6; the published config says 1e-5).

``act`` rounds the activations wherever the server holds them in its
activation dtype: identity for the reference (float32 throughout), a cast
through a narrower type for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.quant import fake_quant

PROJECTIONS = ("wq", "wk", "wv", "wo", "wi_gate", "wi_up")


def serving_weights(params):
    """The weights the reference multiplies by: layer projections at 8 bits
    (per layer, per output channel), everything else as drawn."""
    layers = params["layers"]
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": {"attn": dict(layers["attn"]), "mlp": dict(layers["mlp"])}}
    for block in ("attn", "mlp"):
        for name, w in layers[block].items():
            if name in PROJECTIONS:
                out["layers"][block][name] = fake_quant(w)
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    # x [S, H, hd]; positions 0..S-1
    s, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq     # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w, tokens, model, act=lambda x: x):
    """tokens [S] int32 -> logits [S, vocab] float32."""
    d, nh, nkv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = d // nh
    eps, theta = model["norm_eps"], model["rope_theta"]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    mm = lambda x, m: act(act(x) @ m)

    def layer(h, p):
        a, m = p["attn"], p["mlp"]
        x = act(_rms(h, a["ln"]["scale"], eps))
        q = _rope(mm(x, a["wq"]).reshape(s, nh, hd), theta)
        k = _rope(mm(x, a["wk"]).reshape(s, nkv, hd), theta)
        v = mm(x, a["wv"]).reshape(s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", act(q), act(k)) / np.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = act(jnp.einsum("hqk,khd->qhd", pr, act(v)).reshape(s, nh * hd))
        h = act(h + mm(o, a["wo"]))
        x = act(_rms(h, m["ln"]["scale"], eps))
        u = act(jax.nn.silu(mm(x, m["wi_gate"])) * mm(x, m["wi_up"]))
        return act(h + mm(u, m["wo"])), None

    h = act(jnp.take(w["embed"], tokens, axis=0))
    h, _ = jax.lax.scan(layer, h, w["layers"])
    h = act(_rms(h, w["final_norm"]["scale"], eps))
    return mm(h, w["embed"].T)


@functools.partial(jax.jit, static_argnames=("model_items", "act_dtype"))
def _logits(w, tokens, model_items, act_dtype):
    model = dict(model_items)
    if act_dtype == "float32":
        act = lambda x: x
    else:
        dt = jnp.dtype(act_dtype)
        act = lambda x: x.astype(dt).astype(jnp.float32)
    return forward(w, tokens, model, act)


def logits(w, tokens, model, act_dtype="float32"):
    """Jitted ``forward`` at full float32 precision on the MXU."""
    with jax.default_matmul_precision("highest"):
        return _logits(w, tokens, tuple(sorted(model.items())), act_dtype)
