"""State-space / recurrent blocks: Mamba2 (SSD), mLSTM, sLSTM.

A single chunkwise-parallel primitive (`ssd_chunked`) serves both Mamba2 and
mLSTM — they share the algebra  h_t = a_t h_{t-1} + u_t (b_t outer) ;
y_t = c_t . h_t  with per-step scalar decay ``a_t`` per head.  The chunked
form scans over chunks (O(L/c) sequential steps) and is exact.

mLSTM's normalizer is carried by augmenting the value vector with a constant
1 column, so the same state matrix carries (C, n) — one primitive, two models.

sLSTM is inherently sequential (scalar memories + recurrent gate matrices);
it runs as a lax.scan over time, which is the honest TPU mapping (the paper's
sLSTM admits no chunkwise parallel form).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.layers import matmul_any

# ---------------------------------------------------------------------------
# Chunkwise SSD primitive
# ---------------------------------------------------------------------------

def ssd_chunked(
    u: jax.Array,        # [B, L, H, p]  gated inputs (dt*x or i*v)
    b: jax.Array,        # [B, L, H, n]  input projections (B_t or k_t)
    c: jax.Array,        # [B, L, H, n]  output projections (C_t or q_t)
    log_a: jax.Array,    # [B, L, H]     per-step log decay, <= 0
    chunk: int,
    h0: Optional[jax.Array] = None,   # [B, H, p, n]
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B, L, H, p], h_final [B, H, p, n]).  Exact linear scan.

    Any length: the last chunk is padded with u = 0 and log_a = 0, steps
    that leave the state as it is, and their outputs are dropped."""
    bsz, l, h, p = u.shape
    n = b.shape[-1]
    m = -(-l // chunk)
    f32 = jnp.float32
    u_, b_, c_, la_ = (x.astype(f32) for x in (u, b, c, log_a))
    if m * chunk != l:
        pad = lambda x: jnp.pad(
            x, [(0, 0), (0, m * chunk - l)] + [(0, 0)] * (x.ndim - 2))
        u_, b_, c_, la_ = (pad(x) for x in (u_, b_, c_, la_))
    u_ = u_.reshape(bsz, m, chunk, h, p)
    b_ = b_.reshape(bsz, m, chunk, h, n)
    c_ = c_.reshape(bsz, m, chunk, h, n)
    la_ = la_.reshape(bsz, m, chunk, h)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), f32)

    def chunk_step(h_prev, xs):
        uc, bc, cc, lac = xs                        # [B, c, H, ...]
        cum = jnp.cumsum(lac, axis=1)               # [B, c, H] inclusive
        total = cum[:, -1]                          # [B, H]
        # intra-chunk: y[t] += sum_{s<=t} exp(cum[t]-cum[s]) (c_t.b_s) u_s
        rel = cum[:, :, None, :] - cum[:, None, :, :]          # [B, t, s, H]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
        decay = jnp.where(tri, jnp.exp(rel), 0.0)
        scores = jnp.einsum("bthn,bshn->btsh", cc, bc) * decay
        y_intra = jnp.einsum("btsh,bshp->bthp", scores, uc)
        # inter-chunk: y[t] += c_t . (exp(cum[t]) h_prev)
        y_inter = jnp.einsum("bthn,bhpn->bthp", cc * jnp.exp(cum)[..., None],
                             h_prev)
        # state update: h = exp(total) h_prev + sum_s exp(total-cum[s]) u_s b_s
        carry_decay = jnp.exp(total - 0.0)[..., None, None]
        w = jnp.exp(total[:, None] - cum)                      # [B, c, H]
        h_new = (h_prev * carry_decay
                 + jnp.einsum("bshp,bshn,bsh->bhpn", uc, bc, w))
        return h_new, y_intra + y_inter

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (u_, b_, c_, la_))
    h_final, ys = jax.lax.scan(chunk_step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, m * chunk, h, p)[:, :l]
    return y.astype(u.dtype), h_final


def ssd_step(
    u: jax.Array,       # [B, H, p]
    b: jax.Array,       # [B, H, n]
    c: jax.Array,       # [B, H, n]
    log_a: jax.Array,   # [B, H]
    h: jax.Array,       # [B, H, p, n]
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the same recurrence.

    The head axis is per-layer (`nh = d_inner // ssm_head_dim` for Mamba2,
    `num_heads` for mLSTM) and the cache must carry exactly that extent —
    a cache whose head axis was padded or built against a different head
    count silently broadcasts into garbage, so mismatches fail loudly here
    (the zamba2 hybrid-decode regression: an engine-side pad once stretched
    the state's head axis to the prompt length).
    """
    if h.shape[1] != u.shape[1]:
        raise ValueError(
            f"ssd_step state heads {h.shape[1]} != input heads {u.shape[1]}"
            " — the decode cache does not match this layer's head count")
    f32 = jnp.float32
    a = jnp.exp(log_a.astype(f32))[..., None, None]
    # the outer product u b as a broadcast multiply, not an einsum: XLA
    # then fuses it with the decay into the in-place write of h_new into a
    # donated cache, where a dot would make a state-sized temporary
    h_new = h * a + u.astype(f32)[..., None] * b.astype(f32)[:, :, None, :]
    y = jnp.einsum("bhn,bhpn->bhp", c.astype(f32), h_new)
    return y.astype(u.dtype), h_new


# ---------------------------------------------------------------------------
# Depthwise causal conv (Mamba front conv)
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array,
                state: Optional[jax.Array] = None,
                bias: Optional[jax.Array] = None):
    """x [B, L, C], w [W, C] depthwise, optional bias [C].  Returns
    (silu(conv + bias), new_state [B, W-1, C])."""
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    segs = [xp[:, i:i + x.shape[1], :] * w[i] for i in range(width)]
    y = sum(segs)
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y), xp[:, -(width - 1):, :]


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def mamba2_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    n = cfg.ssm_state
    nh = di // cfg.ssm_head_dim
    ks = jax.random.split(key, 4)
    conv_ch = di + 2 * n
    p = {
        "ln": layers.norm_init(d, cfg.norm),
        "in_proj": layers.dense_init(ks[0], d, 2 * di + 2 * n + nh),
        "conv_w": jax.random.normal(ks[1], (cfg.conv_width, conv_ch),
                                    jnp.float32) * 0.1,
        "a_log": jnp.zeros((nh,), jnp.float32),          # A = -exp(a_log)
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_norm": layers.norm_init(di, "rmsnorm"),
        "out_proj": layers.dense_init(
            ks[2], di, d, scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }
    if cfg.conv_bias:
        p["conv_b"] = jnp.zeros((conv_ch,), jnp.float32)
    return p


def _mamba2_project(p, h, cfg: ModelConfig, dtype):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    n = cfg.ssm_state
    nh = di // cfg.ssm_head_dim
    zxbcdt = matmul_any(h, p["in_proj"], dtype, impl=cfg.impl)
    z, xc, b, c, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    return z, xc, b, c, dt, (di, n, nh)


def mamba2_apply(p, x: jax.Array, cfg: ModelConfig, *, cache=None):
    """Pre-norm Mamba-2 mixer with its residual: x + r * out_proj(
    RMSNorm(y * silu(z))), r = ``cfg.residual_scale``, where

      z, xBC, dt = in_proj(RMSNorm(x)); xBC = silu(conv1d(xBC) + b);
      dt = softplus(dt + dt_bias); A = -exp(A_log);
      h_t = exp(dt A) h_{t-1} + dt x_t (x) B_t;  y_t = C_t . h_t + D x_t

    (one B/C group shared by the heads; the gate multiplies before the
    norm, Mamba-2's ``norm_before_gate=False``).  ``cache`` = (conv_state
    [B, W-1, ch], ssm_state [B, H, p, n]) for one decode token; without
    it the whole sequence runs through ``ssd_chunked`` in chunks of
    ``cfg.ssm_chunk``.  The work between the two
    projections runs under the name scope ``mamba_mixer``, which the HLO
    metadata keeps and a TPU trace drops (the benchmark's
    ``mamba_share.decode`` matches these ops by shape)."""
    dtype = jnp.dtype(cfg.dtype)
    bsz = x.shape[0]
    h = layers.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    z, xc, b, c, dt, (di, n, nh) = _mamba2_project(p, h, cfg, dtype)
    hd = di // nh
    with jax.named_scope("mamba_mixer"):
        conv_in = jnp.concatenate([xc, b, c], axis=-1)
        conv_state = cache[0] if cache is not None else None
        conv_out, conv_state = causal_conv(conv_in, p["conv_w"], conv_state,
                                           p.get("conv_b"))
        xs, b, c = jnp.split(conv_out, [di, di + n], axis=-1)
        a = -jnp.exp(p["a_log"])                              # [H] negative
        log_a = (dt * a).astype(jnp.float32)                  # [B, L, H]
        u = (xs.reshape(bsz, -1, nh, hd).astype(jnp.float32)
             * dt[..., None])                                 # dt-scaled input
        bh = jnp.broadcast_to(b[:, :, None, :], (bsz, b.shape[1], nh, n))
        ch = jnp.broadcast_to(c[:, :, None, :], (bsz, c.shape[1], nh, n))
        if cache is None:
            y, h_final = ssd_chunked(u, bh, ch, log_a,
                                     chunk=min(cfg.ssm_chunk, u.shape[1]))
        else:
            y1, h_final = ssd_step(u[:, 0], bh[:, 0], ch[:, 0], log_a[:, 0],
                                   cache[1])
            y = y1[:, None]
        new_cache = (conv_state, h_final)
        y = y + xs.reshape(bsz, -1, nh, hd) * p["d_skip"][:, None]
        y = y.reshape(bsz, -1, di) * jax.nn.silu(z.astype(jnp.float32))
        y = layers.apply_norm(p["out_norm"], y, "rmsnorm", cfg.norm_eps)
    out = matmul_any(y, p["out_proj"], dtype, impl=cfg.impl)
    return layers.add_residual(x, out, cfg.residual_scale), new_cache


def mamba2_cache_spec(cfg: ModelConfig, batch: int):
    di = cfg.ssm_expand * cfg.d_model
    n = cfg.ssm_state
    nh = di // cfg.ssm_head_dim
    return (
        jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, di + 2 * n),
                             jnp.dtype(cfg.dtype)),
        jax.ShapeDtypeStruct((batch, nh, di // nh, n), jnp.float32),
    )


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM)
# ---------------------------------------------------------------------------

def mlstm_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    nh = cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "ln": layers.norm_init(d, cfg.norm),
        "up": layers.dense_init(ks[0], d, 2 * di),
        "wq": layers.dense_init(ks[1], di, di),
        "wk": layers.dense_init(ks[2], di, di),
        "wv": layers.dense_init(ks[3], di, di),
        "w_if": layers.dense_init(ks[4], di, 2 * nh, scale=0.01),
        "f_bias": jnp.full((nh,), 3.0, jnp.float32),   # open forget gates
        "out_norm": layers.norm_init(di, "rmsnorm"),
        "down": layers.dense_init(
            ks[5], di, d, scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }


def mlstm_apply(p, x: jax.Array, cfg: ModelConfig, *, cache=None,
                chunk: int = 128):
    """cache = state [B, H, hd+1, hd] (value augmented with normalizer row)."""
    dtype = jnp.dtype(cfg.dtype)
    bsz, l = x.shape[:2]
    d = cfg.d_model
    di = cfg.ssm_expand * d
    nh = cfg.num_heads
    hd = di // nh
    h = layers.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    impl = cfg.impl
    u2 = matmul_any(h, p["up"], dtype, impl=impl)
    xm, z = jnp.split(u2, 2, axis=-1)
    q = matmul_any(xm, p["wq"], dtype, impl=impl).reshape(
        bsz, l, nh, hd) / np.sqrt(hd)
    k = matmul_any(xm, p["wk"], dtype, impl=impl).reshape(
        bsz, l, nh, hd) / np.sqrt(hd)
    v = matmul_any(xm, p["wv"], dtype, impl=impl).reshape(bsz, l, nh, hd)
    gif = matmul_any(xm, p["w_if"], jnp.float32, impl=impl)
    ig, fg = jnp.split(gif, 2, axis=-1)                    # [B, L, H]
    log_a = jax.nn.log_sigmoid(fg + p["f_bias"])
    i_lin = jnp.exp(jnp.clip(ig, -10.0, 10.0))
    # augment v with a ones column: state carries (C | n) jointly
    v_aug = jnp.concatenate(
        [v.astype(jnp.float32) * i_lin[..., None],
         i_lin[..., None] * jnp.ones((bsz, l, nh, 1), jnp.float32)], axis=-1)
    if cache is None:
        y_aug, h_final = ssd_chunked(v_aug, k, q, log_a,
                                     chunk=min(chunk, l))
    else:
        y1, h_final = ssd_step(v_aug[:, 0], k[:, 0], q[:, 0], log_a[:, 0],
                               cache)
        y_aug = y1[:, None]
    y_num, y_den = y_aug[..., :hd], y_aug[..., hd:]
    y = y_num / jnp.maximum(jnp.abs(y_den), 1.0)
    y = y.reshape(bsz, -1, di).astype(dtype)
    y = layers.apply_norm(p["out_norm"], y, "rmsnorm",
                          cfg.norm_eps) * jax.nn.silu(
        z.astype(jnp.float32)).astype(dtype)
    out = matmul_any(y, p["down"], dtype, impl=impl)
    return x + out, h_final


def mlstm_cache_spec(cfg: ModelConfig, batch: int):
    di = cfg.ssm_expand * cfg.d_model
    hd = di // cfg.num_heads
    return jax.ShapeDtypeStruct((batch, cfg.num_heads, hd + 1, hd),
                                jnp.float32)


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM) — sequential scalar-memory recurrence
# ---------------------------------------------------------------------------

def slstm_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    ks = jax.random.split(key, 4)
    return {
        "ln": layers.norm_init(d, cfg.norm),
        "w_in": layers.dense_init(ks[0], d, 4 * d),        # z i f o
        "r": jax.random.normal(ks[1], (nh, hd, 4 * hd), jnp.float32) * 0.02,
        "f_bias": jnp.full((d,), 3.0, jnp.float32),
        "out_norm": layers.norm_init(d, "rmsnorm"),
        "w_out": layers.dense_init(
            ks[2], d, d, scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }


def _slstm_cell(p, xt, state, cfg: ModelConfig):
    """xt [B, 4d] pre-proj; state = (c, n, h) each [B, d]."""
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    c_s, n_s, h_s = state
    rec = jnp.einsum("bnh,nhk->bnk", h_s.reshape(-1, nh, hd), p["r"])
    gates = xt + rec.reshape(-1, 4 * d)
    z, i, f, o = jnp.split(gates, 4, axis=-1)
    z = jnp.tanh(z)
    i = jnp.exp(jnp.clip(i, -10.0, 10.0))
    f = jax.nn.sigmoid(f + p["f_bias"])
    o = jax.nn.sigmoid(o)
    c_new = f * c_s + i * z
    n_new = f * n_s + i
    h_new = o * c_new / jnp.maximum(jnp.abs(n_new), 1.0)
    return (c_new, n_new, h_new)


def slstm_apply(p, x: jax.Array, cfg: ModelConfig, *, cache=None):
    """cache = (c, n, h) each [B, d] f32."""
    dtype = jnp.dtype(cfg.dtype)
    bsz, l, d = x.shape
    h0 = layers.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    xt = matmul_any(h0, p["w_in"], jnp.float32, impl=cfg.impl)  # [B, L, 4d]
    if cache is None:
        state = tuple(jnp.zeros((bsz, d), jnp.float32) for _ in range(3))
    else:
        state = cache

    def step(st, xt_t):
        st2 = _slstm_cell(p, xt_t, st, cfg)
        return st2, st2[2]

    if l == 1:
        state = _slstm_cell(p, xt[:, 0], state, cfg)
        ys = state[2][:, None]
    else:
        state, ys = jax.lax.scan(step, state, jnp.moveaxis(xt, 1, 0))
        ys = jnp.moveaxis(ys, 0, 1)
    y = layers.apply_norm(p["out_norm"], ys.astype(dtype), "rmsnorm",
                          cfg.norm_eps)
    out = matmul_any(y, p["w_out"], dtype, impl=cfg.impl)
    return x + out, state


def slstm_cache_spec(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    return tuple(jax.ShapeDtypeStruct((batch, d), jnp.float32)
                 for _ in range(3))
