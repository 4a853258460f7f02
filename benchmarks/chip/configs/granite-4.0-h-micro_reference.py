"""Plain float32 forward of IBM Granite 4.0-H Micro (granitemoehybrid), in
jax.numpy.

ibm-granite/granite-4.0-h-micro: hidden size 2048, 40 layers of which the
configuration keeps the first 20.  ``layer_types`` puts an attention layer
at index 5 of every period of 10 and a Mamba-2 layer everywhere else; every
layer, of either kind, is followed by its own SwiGLU MLP.  With the muP
multipliers (embeddings x 12, residual branches x r = 0.22, logits / 8):

  h = 12 * embed[ids]
  per layer:  h = h + r * mixer(RMSNorm(h));  h = h + r * MLP(RMSNorm(h))
  logits = (RMSNorm(h) @ embed.T) / 8                 (tied head)

  MLP (width 8192):  down(silu(gate(x)) * up(x))
  attention:  32 query heads over 8 key/value heads of 64, no position
              embedding, causal, softmax scale 0.015625 (attention_multiplier)
  Mamba-2 (64 heads of 64, inner width 4096, d_state 128, one B/C group,
  conv width 4 with a bias, no projection bias):
    z | xBC | dt = in_proj(x)                 (4096 | 4096 + 128 + 128 | 64)
    xBC = silu(conv1d(xBC) + b_conv)          causal and depthwise
    x | B | C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        per head, [64, 128]
    y_t = C_t . h_t + D x_t
    out = out_proj(RMSNorm(y * silu(z)))      the gate before the norm
  RMSNorm epsilon 1e-5 everywhere.

One sequence at a time, every position; the recurrence runs token by token
(no chunks), with no cache and no batching.

Departures, each as the serving configuration states it:
  * every layer projection is the 8-bit weight the server multiplies by
    (``quant.fake_quant``: per layer, per output channel); the embedding
    and tied head, the conv, the norms, A_log, dt_bias and D stay float32;
  * 20 of the 40 layers (the first two periods).

``act`` rounds the activations wherever the server holds them in its
activation dtype: identity for the reference (float32 throughout), a cast
through a narrower type for the control.  The server keeps the recurrent
state, the conv and the gated norm in float32, so ``act`` leaves them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip.quant import fake_quant

PROJECTIONS = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "wi_gate",
               "wi_up")
_fake_quant = jax.jit(fake_quant)


def serving_weights(params):
    """The weights the reference multiplies by: layer projections at 8 bits,
    everything else as drawn.  Quantises ``params`` in place, leaf by leaf:
    each float32 projection is dropped as its 8-bit copy takes its place,
    so two float32 trees never live at once."""
    def walk(tree):
        for name in list(tree):
            if isinstance(tree[name], dict):
                walk(tree[name])
            elif name in PROJECTIONS:
                tree[name] = _fake_quant(tree[name])
    walk(params)
    return params


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def forward(w, tokens, model, act=lambda x: x):
    """tokens [S] int32 -> logits [S, vocab] float32."""
    d, nh, nkv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // nh
    eps, r = model["norm_eps"], model["residual_scale"]
    di, n = model["ssm_expand"] * d, model["ssm_state"]
    mp = model["ssm_head_dim"]
    mh = di // mp
    off = model["attn_offset"]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    mm = lambda x, m: act(act(x) @ m)

    def mlp(h, p):
        x = act(_rms(h, p["ln"]["scale"], eps))
        u = act(jax.nn.silu(mm(x, p["wi_gate"])) * mm(x, p["wi_up"]))
        return act(h + r * mm(u, p["wo"]))

    def attention(h, p):
        x = act(_rms(h, p["ln"]["scale"], eps))
        q = mm(x, p["wq"]).reshape(s, nh, hd)
        k = jnp.repeat(mm(x, p["wk"]).reshape(s, nkv, hd), nh // nkv, axis=1)
        v = jnp.repeat(mm(x, p["wv"]).reshape(s, nkv, hd), nh // nkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * model["attn_scale"]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = act(jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, nh * hd))
        return act(h + r * mm(o, p["wo"]))

    def mamba(h, p):
        x = act(_rms(h, p["ln"]["scale"], eps))
        z, xbc, dt = jnp.split(mm(x, p["in_proj"]), [di, 2 * di + 2 * n], -1)
        width = p["conv_w"].shape[0]
        xp = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[-1])), xbc])
        xbc = jax.nn.silu(sum(xp[i:i + s] * p["conv_w"][i]
                              for i in range(width)) + p["conv_b"])
        xs, b, c = jnp.split(xbc, [di, di + n], axis=-1)
        xs = xs.reshape(s, mh, mp)
        dt = jax.nn.softplus(dt + p["dt_bias"])               # [S, heads]
        a = -jnp.exp(p["a_log"])

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t)
            return state, state @ c_t

        _, y = jax.lax.scan(step, jnp.zeros((mh, mp, n)), (xs, b, c, dt))
        y = (y + p["d_skip"][:, None] * xs).reshape(s, di)
        g = _rms(y * jax.nn.silu(z), p["out_norm"]["scale"], eps)
        return act(h + r * mm(g, p["out_proj"]))

    def mambas(h, stack):
        return jax.lax.scan(
            lambda h, p: (mlp(mamba(h, p["mixer"]), p["mlp"]), None),
            h, stack)[0]

    def period(h, p):
        part = lambda lo, hi: jax.tree.map(lambda x: x[lo:hi], p["mamba"])
        h = mambas(h, part(0, off))
        h = mlp(attention(h, p["attn"]["mixer"]), p["attn"]["mlp"])
        return mambas(h, part(off, None)), None

    h = act(jnp.take(w["embed"], tokens, axis=0) * model["embed_scale"])
    h, _ = jax.lax.scan(period, h, w["groups"])
    h = act(_rms(h, w["final_norm"]["scale"], eps))
    return act(mm(h, w["embed"].T) * model["logits_scale"])


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
