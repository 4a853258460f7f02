"""Shared neural-net layers: norms, linears (float or kneaded), RoPE,
activations, and attention in four execution regimes:

  * full    — materialized scores, small sequences (smoke tests, cross-attn)
  * masked  — blockwise online-softmax, causal blocks masked but computed
              (the naive baseline; 2x causal FLOP waste, kept for §Perf)
  * flash   — pair-list blockwise attention with custom_vjp: exact causal
              FLOPs, O(S) memory (the production path)
  * decode  — one query step against a KV cache

All weights are stored f32 and cast to the compute dtype at use.  Any linear
weight leaf may be replaced by a `QuantizedTensor` / `KneadedWeight` /
`PackedInt4` for the Tetris serving path — `matmul_any` dispatches.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kneading import KneadedWeight, ShardedKneadedWeight
from repro.core.quantization import QuantizedTensor

# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, scale: float = 0.02) -> jax.Array:
    return (jax.random.truncated_normal(key, -2, 2, (d_in, d_out), jnp.float32)
            * scale)


# ---------------------------------------------------------------------------
# Quantized weight container for the int4 serving mode
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedInt4:
    """Nibble-packed int4 weight [K/2, N] + per-channel scale (serving)."""

    packed: jax.Array
    scale: jax.Array
    k: int = dataclasses.field(metadata=dict(static=True), default=0)


def matmul_any(x: jax.Array, w, compute_dtype=jnp.bfloat16,
               impl: str = "int") -> jax.Array:
    """x @ w for float, QuantizedTensor (int8), KneadedWeight, or PackedInt4.

    Quantized paths follow SAC: integer-code contraction with the per-channel
    scale applied once in the epilogue (never dequantize weights up front in
    a separate HBM-visible buffer).  ``impl`` selects the SAC execution path
    for KneadedWeight leaves ("float"/"int"/"planes"/"pallas"); float leaves
    ignore it.  N-sharded kneaded leaves (per-layer scan slices of a
    ``ShardedStackedKneadedWeight``, or plain ``ShardedKneadedWeight``)
    dispatch through the sharded Pallas entry under the serving mesh
    (docs/DESIGN.md §8).
    """
    if isinstance(w, (KneadedWeight, ShardedKneadedWeight)):
        from repro.core.sac import sac_matmul
        return sac_matmul(x, w, impl=impl).astype(compute_dtype)
    if isinstance(w, QuantizedTensor):
        out = jnp.einsum("...k,kn->...n", x.astype(compute_dtype),
                         w.q.astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        return (out * w.scale).astype(compute_dtype)
    if isinstance(w, PackedInt4):
        from repro.kernels.kneaded_gemm.ref import unpack_int4
        q = unpack_int4(w.packed)
        out = jnp.einsum("...k,kn->...n", x.astype(compute_dtype),
                         q.astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        return (out * w.scale).astype(compute_dtype)
    # preferred_element_type == compute dtype, NOT the jnp default (f32):
    # with the contraction dim sharded, SPMD all-reduces the dot's partial
    # sums — at f32 that is 2x the bytes of every TP collective (measured:
    # the top-5 collectives on llama3 train were f32 activation reductions).
    # The MXU still accumulates f32 within a shard; only the cross-shard
    # combine is bf16 (standard tensor-parallel practice).
    return jnp.einsum("...k,kn->...n", x.astype(compute_dtype),
                      w.astype(compute_dtype),
                      preferred_element_type=compute_dtype)


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(p, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * p["scale"]).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf * p["scale"] + p["bias"]).astype(x.dtype)


def add_residual(x: jax.Array, y: jax.Array, scale: float = 1.0) -> jax.Array:
    """``x + scale * y``: a residual branch under a muP residual multiplier
    (granite's 0.22); at 1.0 the branch adds as it is."""
    return x + (y if scale == 1.0 else y * scale)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMSNorm over the head dim (qwen3 qk-norm)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":                      # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, ..., hd]; positions: [B, S] int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq     # [B, S, half]
    # broadcast over head axes between S and hd
    extra = x.ndim - 3
    ang = ang.reshape(ang.shape[:2] + (1,) * extra + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA layout: q [B,S,KV,G,hd], k/v [B,S,KV,hd])
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def quantize_kv(x: jax.Array):
    """int8-quantize a KV tensor [..., hd] with per-row (pos, head) scales.

    The paper's "fewer effective bits" applied to the decode-dominant byte
    stream: the KV cache.  Returns (codes int8 [..., hd], scale f32 [...])."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def _scores(q, k, scale):
    # q: [B,Sq,KV,G,hd], k: [B,Sk,KV,hd] -> [B,KV,G,Sq,Sk]
    return jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def full_attention(q, k, v, *, causal: bool, window: int = 0,
                   kv_offset: int = 0) -> jax.Array:
    """Reference attention, materializes scores (small S only)."""
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    s = _scores(q, k, 1.0 / np.sqrt(hd))
    qpos = kv_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _chunk_pairs(nq: int, nk: int, causal: bool, window_chunks: int):
    """Static (qi, ki) chunk-pair list for exact-FLOP blockwise attention."""
    pairs = []
    for qi in range(nq):
        lo = 0 if not window_chunks else max(0, qi - window_chunks)
        hi = (qi + 1) if causal else nk
        for ki in range(lo, hi):
            pairs.append((qi, ki))
    return np.array(pairs, np.int32)


def _block_attend(qc, kc, vc, qi, ki, chunk, causal, window, scale):
    """One chunk pair -> (m, l, o) partials.  qc: [B,cq,KV,G,hd]."""
    s = _scores(qc, kc, scale)                               # [B,KV,G,cq,ck]
    qpos = qi * chunk + jnp.arange(qc.shape[1])[:, None]
    kpos = ki * chunk + jnp.arange(kc.shape[1])[None, :]
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,KV,G,cq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, vc.astype(jnp.float32))
    return m, l, o


def chunked_attention(q, k, v, *, causal: bool, chunk: int, window: int = 0,
                      exact: bool = True) -> jax.Array:
    """Blockwise online-softmax attention.

    exact=True  : scan over the lower-triangle chunk-pair list only
                  (HLO FLOPs == true causal FLOPs).
    exact=False : scan over the full chunk grid with masking (baseline).
    """
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    assert sq % chunk == 0 and sk % chunk == 0, (sq, sk, chunk)
    nq, nk = sq // chunk, sk // chunk
    scale = 1.0 / np.sqrt(hd)
    wc = (window + chunk - 1) // chunk if window else 0

    qch = q.reshape(b, nq, chunk, kvh, g, hd)
    kch = k.reshape(b, nk, chunk, kvh, hd)
    vch = v.reshape(b, nk, chunk, kvh, hd)

    if exact:
        pairs = _chunk_pairs(nq, nk, causal, wc)
        # carry: running (m, l, o) for every q chunk; one dynamic-slice update
        # per visited pair.  FLOPs = exactly the unmasked pair count.
        m0 = jnp.full((nq, b, kvh, g, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((nq, b, kvh, g, chunk), jnp.float32)
        o0 = jnp.zeros((nq, b, kvh, g, chunk, hd), jnp.float32)

        def step(carry, pair):
            m_all, l_all, o_all = carry
            qi, ki = pair[0], pair[1]
            qc = jax.lax.dynamic_index_in_dim(qch, qi, 1, keepdims=False)
            kc = jax.lax.dynamic_index_in_dim(kch, ki, 1, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vch, ki, 1, keepdims=False)
            mb, lb, ob = _block_attend(qc, kc, vc, qi, ki, chunk, causal,
                                       window, scale)
            m_old = m_all[qi]
            l_old = l_all[qi]
            o_old = o_all[qi]
            m_new = jnp.maximum(m_old, mb)
            c_old = jnp.exp(m_old - m_new)
            c_blk = jnp.exp(mb - m_new)
            l_new = l_old * c_old + lb * c_blk
            o_new = o_old * c_old[..., None] + ob * c_blk[..., None]
            return ((m_all.at[qi].set(m_new), l_all.at[qi].set(l_new),
                     o_all.at[qi].set(o_new)), None)

        (m_all, l_all, o_all), _ = jax.lax.scan(step, (m0, l0, o0),
                                                jnp.asarray(pairs))
        out = o_all / jnp.maximum(l_all[..., None], 1e-30)    # [nq,B,KV,G,c,hd]
        out = jnp.transpose(out, (1, 0, 4, 2, 3, 5))          # [B,nq,c,KV,G,hd]
        return out.reshape(b, sq, kvh, g, hd).astype(q.dtype)

    # --- masked baseline: every (qi, ki) pair computed, causal blocks masked
    def per_q_chunk(args):
        qi, qc = args

        def kv_step(carry, args2):
            ki, kc, vc = args2
            m_old, l_old, o_old = carry
            mb, lb, ob = _block_attend(qc, kc, vc, qi, ki, chunk, causal,
                                       window, scale)
            m_new = jnp.maximum(m_old, mb)
            c_old = jnp.exp(m_old - m_new)
            c_blk = jnp.exp(mb - m_new)
            return (m_new, l_old * c_old + lb * c_blk,
                    o_old * c_old[..., None] + ob * c_blk[..., None]), None

        init = (jnp.full((b, kvh, g, chunk), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, g, chunk), jnp.float32),
                jnp.zeros((b, kvh, g, chunk, hd), jnp.float32))
        (m, l, o), _ = jax.lax.scan(
            kv_step, init,
            (jnp.arange(nk), jnp.moveaxis(kch, 1, 0), jnp.moveaxis(vch, 1, 0)))
        return o / jnp.maximum(l[..., None], 1e-30)

    out = jax.lax.map(per_q_chunk, (jnp.arange(nq), jnp.moveaxis(qch, 1, 0)))
    # out: [nq, B, KV, G, c, hd] -> [B, S, KV, G, hd]
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5))
    return out.reshape(b, sq, kvh, g, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos: jax.Array,
                     window: int = 0) -> jax.Array:
    """One-step attention: q [B,1,KV,G,hd] vs cache [B,Smax,KV,hd].

    ``pos`` [B] is the index of the *current* token (cache valid < pos+1).
    """
    b, _, kvh, g, hd = q.shape
    smax = k_cache.shape[1]
    s = _scores(q, k_cache, 1.0 / np.sqrt(hd))                # [B,KV,G,1,Smax]
    kpos = jnp.arange(smax)[None, :]
    valid = kpos <= pos[:, None]
    if window:
        valid &= kpos > pos[:, None] - window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_append(q, k_cache, v_cache, k_new, v_new,
                            pos: jax.Array, window: int = 0) -> jax.Array:
    """:func:`decode_attention` on a cache that does not hold the new token.

    q [B,1,KV,G,hd] attends to the cache's positions ``< pos`` and to the
    new token's own ``k_new``/``v_new`` [B,1,KV,hd] as one more term of the
    same softmax: the keys of ``decode_attention`` on a cache written at
    ``pos``, with the cache left read-only.  Pass the new K/V as the cache
    will store them (rounded to its dtype, or dequantised).
    """
    smax = k_cache.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = _scores(q, k_cache, scale)                            # [B,KV,G,1,Smax]
    kpos = jnp.arange(smax)[None, :]
    valid = kpos < pos[:, None]
    if window:
        valid &= kpos > pos[:, None] - window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    s = jnp.concatenate([s, _scores(q, k_new, scale)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    out = (jnp.einsum("bkgqs,bskh->bqkgh", p[..., :smax],
                      v_cache.astype(jnp.float32))
           + jnp.einsum("bkgqs,bskh->bqkgh", p[..., smax:],
                        v_new.astype(jnp.float32)))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash attention (pair-list, custom_vjp): exact causal FLOPs, O(S) memory.
# The forward is the `exact` path above; the custom backward recomputes
# per-pair probabilities from (q, k, v, lse) — no online-softmax carries or
# block masks are ever saved (the failure mode of the masked baseline, see
# EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

def _batch_only(x, batch_axis=1):
    """Pin a flash-loop tensor to batch-only sharding (heads replicated).

    For archs whose kv-head count does not divide the TP degree, GSPMD
    replicates attention heads; without pinning, the scan carries and chunk
    stacks pick inconsistent layouts and every pair step re-gathers its
    operands (measured: 5.8 TiB/device/step on nemotron train).  Pinning
    everything batch-only makes the replication explicit and one-time."""
    from repro.runtime import pspec
    spec = [None] * x.ndim
    spec[batch_axis] = "batch"
    return pspec.constrain(x, *spec)


def _flash_fwd_impl(q, k, v, causal, chunk, window, replicate_heads=False):
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    nq, nk = sq // chunk, sk // chunk
    scale = 1.0 / np.sqrt(hd)
    wc = (window + chunk - 1) // chunk if window else 0
    pairs = _chunk_pairs(nq, nk, causal, wc)
    qch = jnp.moveaxis(q.reshape(b, nq, chunk, kvh, g, hd), 1, 0)
    kch = jnp.moveaxis(k.reshape(b, nk, chunk, kvh, hd), 1, 0)
    vch = jnp.moveaxis(v.reshape(b, nk, chunk, kvh, hd), 1, 0)

    m0 = jnp.full((nq, b, kvh, g, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, b, kvh, g, chunk), jnp.float32)
    o0 = jnp.zeros((nq, b, kvh, g, chunk, hd), jnp.float32)
    if replicate_heads:
        qch, kch, vch, m0, l0, o0 = (
            _batch_only(t) for t in (qch, kch, vch, m0, l0, o0))

    def step(carry, pair):
        m_all, l_all, o_all = carry
        qi, ki = pair[0], pair[1]
        mb, lb, ob = _block_attend(qch[qi], kch[ki], vch[ki], qi, ki, chunk,
                                   causal, window, scale)
        m_old, l_old, o_old = m_all[qi], l_all[qi], o_all[qi]
        m_new = jnp.maximum(m_old, mb)
        c_old = jnp.exp(m_old - m_new)
        c_blk = jnp.exp(mb - m_new)
        return ((m_all.at[qi].set(m_new),
                 l_all.at[qi].set(l_old * c_old + lb * c_blk),
                 o_all.at[qi].set(o_old * c_old[..., None]
                                  + ob * c_blk[..., None])), None)

    (m_all, l_all, o_all), _ = jax.lax.scan(step, (m0, l0, o0),
                                            jnp.asarray(pairs))
    lse = m_all + jnp.log(jnp.maximum(l_all, 1e-30))     # [nq,B,KV,G,c]
    out = o_all / jnp.maximum(l_all[..., None], 1e-30)
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(b, sq, kvh, g, hd)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool, chunk: int, window: int,
                    replicate_heads: bool = False):
    out, _ = _flash_fwd_impl(q, k, v, causal, chunk, window, replicate_heads)
    return out


def _flash_vjp_fwd(q, k, v, causal, chunk, window, replicate_heads):
    out, lse = _flash_fwd_impl(q, k, v, causal, chunk, window,
                               replicate_heads)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, chunk, window, replicate_heads, res, do):
    q, k, v, out, lse = res
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    nq, nk = sq // chunk, sk // chunk
    scale = 1.0 / np.sqrt(hd)
    wc = (window + chunk - 1) // chunk if window else 0
    pairs = _chunk_pairs(nq, nk, causal, wc)

    f32 = jnp.float32
    qch = jnp.moveaxis(q.reshape(b, nq, chunk, kvh, g, hd), 1, 0).astype(f32)
    kch = jnp.moveaxis(k.reshape(b, nk, chunk, kvh, hd), 1, 0).astype(f32)
    vch = jnp.moveaxis(v.reshape(b, nk, chunk, kvh, hd), 1, 0).astype(f32)
    doch = jnp.moveaxis(do.reshape(b, nq, chunk, kvh, g, hd), 1, 0).astype(f32)
    # delta[i] = rowsum(do * out)
    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1)  # [B,S,KV,G]
    delta = jnp.moveaxis(
        delta.reshape(b, nq, chunk, kvh, g), 1, 0)              # [nq,B,c,KV,G]
    # lse from fwd: [nq,B,KV,G,c] -> match [nq,B,c,KV,G]
    lse_t = jnp.transpose(lse, (0, 1, 4, 2, 3))

    dq0 = jnp.zeros((nq, b, chunk, kvh, g, hd), f32)
    dk0 = jnp.zeros((nk, b, chunk, kvh, hd), f32)
    dv0 = jnp.zeros((nk, b, chunk, kvh, hd), f32)
    if replicate_heads:
        qch, kch, vch, doch, delta, lse_t, dq0, dk0, dv0 = (
            _batch_only(t) for t in (qch, kch, vch, doch, delta, lse_t,
                                     dq0, dk0, dv0))

    def step(carry, pair):
        dq_all, dk_all, dv_all = carry
        qi, ki = pair[0], pair[1]
        qc, kc, vc, doc = qch[qi], kch[ki], vch[ki], doch[qi]
        s = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc) * scale
        qpos = qi * chunk + jnp.arange(chunk)[:, None]
        kpos = ki * chunk + jnp.arange(chunk)[None, :]
        mask = jnp.ones((chunk, chunk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        # p = exp(s - lse): true softmax probabilities of this block
        p = jnp.exp(s - jnp.transpose(lse_t[qi], (0, 2, 3, 1))[..., None])
        dv_blk = jnp.einsum("bkgqs,bqkgh->bskh", p, doc)
        dp = jnp.einsum("bqkgh,bskh->bkgqs", doc, vc)
        dlt = jnp.transpose(delta[qi], (0, 2, 3, 1))[..., None]  # [B,KV,G,c,1]
        ds = p * (dp - dlt) * scale
        dq_blk = jnp.einsum("bkgqs,bskh->bqkgh", ds, kc)
        dk_blk = jnp.einsum("bkgqs,bqkgh->bskh", ds, qc)
        return ((dq_all.at[qi].add(dq_blk),
                 dk_all.at[ki].add(dk_blk),
                 dv_all.at[ki].add(dv_blk)), None)

    (dq_all, dk_all, dv_all), _ = jax.lax.scan(step, (dq0, dk0, dv0),
                                               jnp.asarray(pairs))
    dq = jnp.moveaxis(dq_all, 0, 1).reshape(b, sq, kvh, g, hd).astype(q.dtype)
    dk = jnp.moveaxis(dk_all, 0, 1).reshape(b, sk, kvh, hd).astype(k.dtype)
    dv = jnp.moveaxis(dv_all, 0, 1).reshape(b, sk, kvh, hd).astype(v.dtype)
    return dq, dk, dv


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attend(q, k, v, *, causal: bool, impl: str, chunk: int,
           window: int = 0, replicate_heads: bool = False) -> jax.Array:
    """Dispatch on sequence length / implementation choice.

    impl="flash"  : pair-list exact-FLOP blockwise attention w/ custom vjp
    impl="masked" : chunked online-softmax, every block computed+masked
                    (the naive baseline; kept for §Perf comparisons)
    """
    sq, sk = q.shape[1], k.shape[1]
    if max(sq, sk) <= max(chunk, 512) or sq % chunk or sk % chunk:
        return full_attention(q, k, v, causal=causal, window=window)
    if impl == "flash":
        return flash_attention(q, k, v, causal, chunk, window,
                               replicate_heads)
    return chunked_attention(q, k, v, causal=causal, chunk=chunk,
                             window=window, exact=False)
