"""Transformer blocks: GQA attention, dense MLP, and capacity-based MoE.

All blocks are functional: ``*_init(key, cfg) -> params`` and
``*_apply(params, x, ...) -> y``.  Params are plain dicts of f32 arrays so a
stack of layers can be created with vmap and scanned over.

MoE follows the expert-parallel design in docs/DESIGN.md §3: routing is computed
replicated (router weight is tiny), dispatch/expert-compute/combine run under
``shard_map`` with experts sharded on the "model" axis and one psum to
combine — the same reduction pattern as Megatron TP, so no extra collective
class is introduced.  Without a mesh the identical dispatch code runs with
all experts local (smoke tests).

Kneaded expert banks (docs/DESIGN.md §13) take a second serving path: when
``p["wi"]``/``p["wo"]`` are stacked :class:`~repro.core.kneading.KneadedWeight`
banks ([E, K, N] per layer), the capacity-padded dense einsum is replaced by a
per-expert walk — each local expert's routed rows ([cap, D], M <= 8 at decode)
run through the SAC kernel's decode-GEMV fast path with the activation-skip
mask computed from exactly those routed rows.  Experts shard over the
dedicated "expert" mesh axis (the "model" axis keeps N-sharding the dense
projections); slot routing and the f32 scatter-add combine are shared with
the dense path, so EP == all-local stays bit-exact through the psum.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.layers import matmul_any
from repro.runtime import pspec

# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, d_model: Optional[int] = None,
              cross: bool = False) -> dict:
    d = d_model or cfg.d_model
    hd, nh, nkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 5)
    p = {
        "ln": layers.norm_init(d, cfg.norm),
        "wq": layers.dense_init(ks[0], d, nh * hd),
        "wk": layers.dense_init(ks[1], d, nkv * hd),
        "wv": layers.dense_init(ks[2], d, nkv * hd),
        "wo": layers.dense_init(ks[3], nh * hd, d,
                                scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qk_norm:
        p["qnorm"] = jnp.ones((hd,), jnp.float32)
        p["knorm"] = jnp.ones((hd,), jnp.float32)
    if cross:
        p["ln_kv"] = layers.norm_init(d, cfg.norm)
    return p


def res_constrain(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Residual-stream constraint: batch-sharded always; sequence-parallel
    (Megatron SP: residuals sharded over "model" on the seq axis) when the
    config enables it, cutting the per-layer activation footprint (and remat
    carries) by the TP degree."""
    seq = "seq" if x.ndim >= 3 and cfg.sequence_parallel else None
    return pspec.constrain(x, *(["batch", seq] + [None] * (x.ndim - 2)))


def sp_gather(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Megatron-SP's explicit activation all-gather before a TP matmul.

    With the seq axis sharded over "model" THROUGH a matmul, the partitioner
    cannot also keep the weight TP-sharded on "model" — it falls back to a
    FULL weight all-gather (measured on nemotron train: f32[18432,73728]
    gathered per layer per microbatch, 3.9 TiB/device/step).  Re-gathering
    the (much smaller) activations here frees the model axis for the weight,
    restoring proper TP: AG(x over seq) + RS(y over seq) replaces the
    catastrophic weight gathers.  §Perf nemotron iteration."""
    if not cfg.sequence_parallel or not cfg.sp_matmul_gather or x.ndim < 3:
        return x
    return pspec.constrain(x, *(["batch"] + [None] * (x.ndim - 1)))


def _model_degree() -> int:
    """Devices the logical "model" axis spans under the installed mesh
    (1 without one)."""
    mesh = pspec.current_mesh()
    if mesh is None:
        return 1
    axes = [a for a in pspec.current_rules().get("model", ())
            if a in mesh.axis_names]
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def cache_seq_sharded() -> bool:
    """Whether a decode cache's sequence axis may be split over devices:
    the decode runs under a mesh whose "model" axis spans more than one
    device (``sharding.cache_spec_sharding`` shards long caches there)."""
    return _model_degree() > 1


def _attn_shard_mode(cfg: ModelConfig):
    """How to shard attention tensors over the "model" axes.

    "kv" when the kv-head count divides the TP degree (fully local
    attention); else "hd" (head_dim sharded; the score contraction psums —
    ~8x less traffic than the replicated-head fallback GSPMD chooses on its
    own, which all-gathers q/k/v inside every flash-attention step).
    """
    n = _model_degree()
    if n <= 1:
        return None
    if cfg.num_kv_heads % n == 0:
        return "kv"
    # Two alternatives for kv_heads % TP != 0 were tried and REFUTED
    # (EXPERIMENTS.md §Perf, arctic iterations 5a/5b):
    #   "hd" (shard head_dim, psum scores): flash score blocks are
    #        cq*ck >> q/k/v chunks -> 4x MORE traffic (74s vs 19s);
    #   "q_heads" (shard padded G, replicate k/v): the un-constrained flash
    #        (m,l,o) carries re-gather per pair step -> 36s vs 19s.
    # GSPMD's replicated-head fallback is the best known layout here;
    # proper 2D flash sharding needs carry constraints — future work.
    return None


def _qkv(p, x, kv_src, cfg: ModelConfig, dtype):
    b = x.shape[0]
    hd, nh, nkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    g = nh // nkv
    impl, skip = cfg.impl, cfg.activation_skip
    q = matmul_any(x, p["wq"], dtype, impl=impl,
                   skip_activations=skip).reshape(b, -1, nkv, g, hd)
    k = matmul_any(kv_src, p["wk"], dtype, impl=impl,
                   skip_activations=skip).reshape(b, -1, nkv, hd)
    v = matmul_any(kv_src, p["wv"], dtype, impl=impl,
                   skip_activations=skip).reshape(b, -1, nkv, hd)
    if cfg.qk_norm:
        q = layers.rms_head_norm(q, p["qnorm"], cfg.norm_eps)
        k = layers.rms_head_norm(k, p["knorm"], cfg.norm_eps)
    if cfg.attn_scale:
        # every attention kernel scales scores by 1/sqrt(hd); a model's own
        # softmax scale is folded into q (exact when the ratio is a power
        # of two, as granite's 1/64 over 1/8 is)
        q = q * (cfg.attn_scale * float(np.sqrt(hd)))
    mode = _attn_shard_mode(cfg)
    if mode == "kv":
        q = pspec.constrain(q, "batch", None, "model", None, None)
        k = pspec.constrain(k, "batch", None, "model", None)
        v = pspec.constrain(v, "batch", None, "model", None)
    return q, k, v


def attn_apply(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    kv_src: Optional[jax.Array] = None,          # cross-attention source
    kv_const: Optional[Tuple[jax.Array, jax.Array]] = None,  # precomputed k,v
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,     # decode KV cache
    pos: Optional[jax.Array] = None,             # decode position [B]
    return_kv: bool = False,
):
    """Pre-norm attention block.  Returns (y, new_cache_or_kv_or_None).

    With ``cache`` (a decode step), ``return_kv`` leaves the cache read-only
    and returns the new token's entries as the cache stores them, for the
    caller to write at ``pos``; without it the layer's cache slice comes
    back written."""
    dtype = jnp.dtype(cfg.dtype)
    h = sp_gather(layers.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps), cfg)
    use_rope = cfg.positional == "rope"

    if cache is not None:                         # ---- decode step
        q, k_new, v_new = _qkv(p, h, h, cfg, dtype)
        if use_rope:
            posb = pos[:, None]
            q = layers.apply_rope(q, posb, cfg.rope_theta)
            k_new = layers.apply_rope(k_new, posb, cfg.rope_theta)
        if len(cache) == 4:                       # (k8, v8, k_scale, v_scale)
            # knead the cache like the weights: int8 codes + per-(pos, head)
            # scale, read back dequantised
            (k8, ks), (v8, vs) = (layers.quantize_kv(k_new),
                                  layers.quantize_kv(v_new))
            new = (k8, v8, ks, vs)
            read = lambda c: (c[0].astype(jnp.float32) * c[2][..., None],
                              c[1].astype(jnp.float32) * c[3][..., None])
        else:
            new = (k_new.astype(cache[0].dtype), v_new.astype(cache[1].dtype))
            read = lambda c: (c[0], c[1])
        if return_kv:
            # The caller writes ``new`` in place after the layer scan
            # (LanguageModel.decode_step): attend to the old cache plus the
            # new token, so no layer slice is rewritten.
            out = layers.decode_attention_append(q, *read(cache), *read(new),
                                                 pos, window=cfg.window)
            cache = new
        else:
            # A cache whose seq axis a mesh may shard (cache_seq_sharded):
            # write by masked select, NOT dynamic_update_slice, which on a
            # sharded axis makes SPMD gather the whole cache.  The where()
            # is a local masked write on every shard, at the price of
            # rewriting the whole layer slice.
            write = jnp.arange(cache[0].shape[1])[None, :] == pos[:, None]
            cache = tuple(
                jnp.where(write.reshape(write.shape + (1,) * (c.ndim - 2)),
                          n, c)
                for c, n in zip(cache, new))
            out = layers.decode_attention(q, *read(cache), pos,
                                          window=cfg.window)
        y = matmul_any(out.reshape(out.shape[0], 1, -1), p["wo"], dtype,
                       impl=cfg.impl, skip_activations=cfg.activation_skip)
        return layers.add_residual(x, y, cfg.residual_scale), cache

    if kv_const is not None:                      # ---- cross-attn w/ cached KV
        k, v = kv_const
        q, _, _ = _qkv(p, h, h[:, :1], cfg, dtype)  # kv path unused
        # no RoPE on cross-attention queries (positions are heterogeneous)
        out = layers.attend(q, k, v, causal=False, impl=cfg.attn_impl,
                            chunk=cfg.attn_chunk,
                            replicate_heads=cfg.flash_replicate_pin
                            and _attn_shard_mode(cfg) is None
                            and pspec.current_mesh() is not None)
    else:
        src = kv_src if kv_src is not None else h
        if kv_src is not None:
            src = layers.apply_norm(p["ln_kv"], src, cfg.norm, cfg.norm_eps) \
                if "ln_kv" in p else src
        q, k, v = _qkv(p, h, src, cfg, dtype)
        if use_rope and kv_src is None:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
        out = layers.attend(q, k, v, causal=causal and kv_src is None,
                            impl=cfg.attn_impl, chunk=cfg.attn_chunk,
                            window=cfg.window,
                            replicate_heads=cfg.flash_replicate_pin
                            and _attn_shard_mode(cfg) is None
                            and pspec.current_mesh() is not None)
    b, s = out.shape[:2]
    y = matmul_any(out.reshape(b, s, -1), p["wo"], dtype, impl=cfg.impl,
                   skip_activations=cfg.activation_skip)
    y = res_constrain(layers.add_residual(x, y, cfg.residual_scale), cfg)
    if return_kv:
        return y, (k, v)
    return y, None


# ---------------------------------------------------------------------------
# Dense MLP block
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    f = d_ff or cfg.d_ff
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln": layers.norm_init(d, cfg.norm),
        "wo": layers.dense_init(k2, f, d,
                                scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }
    if cfg.activation == "swiglu":
        # SEPARATE gate/up projections, not a fused [D, 2F] + split: the
        # split of a "model"-sharded 2F dim makes the partitioner give up
        # on the TP layout entirely (measured on vlm train: full f32 weight
        # all-gathers, 1.1 TiB/device/step — §Perf iteration E).
        p["wi_gate"] = layers.dense_init(k1, d, f)
        p["wi_up"] = layers.dense_init(k3, d, f)
    else:
        p["wi"] = layers.dense_init(k1, d, f)
    return p


def _ffn(h, p, activation: str, dtype, impl: str = "int",
         skip: bool = False) -> jax.Array:
    if activation == "swiglu":
        u = (jax.nn.silu(matmul_any(h, p["wi_gate"], dtype, impl=impl,
                                    skip_activations=skip))
             * matmul_any(h, p["wi_up"], dtype, impl=impl,
                          skip_activations=skip))
    else:
        u = layers.activate(matmul_any(h, p["wi"], dtype, impl=impl,
                                       skip_activations=skip),
                            activation)
    return matmul_any(u, p["wo"], dtype, impl=impl, skip_activations=skip)


def mlp_apply(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dtype = jnp.dtype(cfg.dtype)
    h = sp_gather(layers.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps), cfg)
    y = _ffn(h, p, cfg.activation, dtype, impl=cfg.impl,
             skip=cfg.activation_skip)
    return res_constrain(layers.add_residual(x, y, cfg.residual_scale), cfg)


# ---------------------------------------------------------------------------
# MoE block (capacity-based dispatch, EP over "model")
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> dict:
    d, e = cfg.d_model, cfg.num_experts
    f = cfg.moe_dff or cfg.d_ff
    ks = jax.random.split(key, 4)
    wi_out = 2 * f if cfg.activation == "swiglu" else f
    p = {
        "ln": layers.norm_init(d, cfg.norm),
        "router": layers.dense_init(ks[0], d, e, scale=0.02),
        "wi": jax.vmap(lambda k: layers.dense_init(k, d, wi_out))(
            jax.random.split(ks[1], e)),
        "wo": jax.vmap(lambda k: layers.dense_init(
            k, f, d, scale=0.02 / np.sqrt(2 * cfg.num_layers)))(
            jax.random.split(ks[2], e)),
    }
    if cfg.dense_residual:
        p["dense"] = mlp_init(ks[3], cfg)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cap = int(np.ceil(n_tokens * cfg.top_k * cfg.capacity_factor
                      / cfg.num_experts))
    return max(1, min(n_tokens, max(cap, 4)))


def _split_quant(w):
    """Maybe-quantized weight -> (codes_or_float, scale_or_None, packed4)."""
    from repro.core.quantization import QuantizedTensor
    from repro.models.layers import PackedInt4
    if isinstance(w, QuantizedTensor):
        return w.q, w.scale, False
    if isinstance(w, PackedInt4):
        return w.packed, w.scale, True
    return w, None, False


def _expert_matmul(xg, q, scale, packed4, dtype):
    """[E, C, D] @ per-expert [E, D', F] with SAC epilogue scaling."""
    if packed4:
        from repro.kernels.kneaded_gemm.ref import unpack_int4
        q = jax.vmap(unpack_int4)(q)
    h = jnp.einsum("ecd,edf->ecf", xg.astype(dtype), q.astype(dtype),
                   preferred_element_type=dtype)
    if scale is not None:
        h = (h.astype(jnp.float32) * scale).astype(dtype)
    return h


def _route_slots(x2d, eids, gates, e_loc: int, e_offset, cap: int):
    """Capacity-slot routing shared by the dense and kneaded expert paths.

    Computes, for the local expert slice [e_loc], which token feeds each
    (expert, capacity) slot and gathers those rows.  Returns
    ``(xg [e_loc, cap, D], disp [e_loc*cap], slot_gate [e_loc*cap])``.
    Sharing this (and :func:`_combine_slots`) between the paths is
    load-bearing for bit-exactness: identical slot order means identical
    f32 scatter-add pairing in the combine, so kneaded EP == all-local
    reduces in the same order the dense path always has.
    """
    t, d = x2d.shape
    k = eids.shape[1]
    flat_e = eids.reshape(-1)                       # [T*k]
    flat_g = gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    local = flat_e - e_offset                       # [T*k] local expert index
    oh = jax.nn.one_hot(local, e_loc, dtype=jnp.int32)   # out-of-range -> 0
    position = jnp.cumsum(oh, axis=0) - oh               # slots used before me
    mypos = jnp.sum(position * oh, axis=1)
    valid = (oh.sum(axis=1) > 0) & (mypos < cap)
    slot = jnp.where(valid, local * cap + mypos, e_loc * cap)  # overflow bin
    # dispatch indices: which token feeds each (expert, capacity) slot
    disp = jnp.full((e_loc * cap + 1,), t, jnp.int32).at[slot].set(
        jnp.where(valid, tok_idx, t))[:-1]
    slot_gate = jnp.zeros((e_loc * cap + 1,), jnp.float32).at[slot].set(
        jnp.where(valid, flat_g, 0.0))[:-1]
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    xg = x_pad[disp].reshape(e_loc, cap, d)              # gather
    return xg, disp, slot_gate


def _combine_slots(y, disp, slot_gate, t: int, out_dtype):
    """Gate-weighted f32 scatter-add of per-slot outputs back to tokens."""
    d = y.shape[-1]
    y_flat = y.reshape(-1, d).astype(jnp.float32) * slot_gate[:, None]
    out = jnp.zeros((t + 1, d), jnp.float32).at[disp].add(y_flat)[:-1]
    return out.astype(out_dtype)


def _dispatch_compute(x2d, eids, gates, wi, wi_scale, wo, wo_scale,
                      *, cfg: ModelConfig, e_offset, cap: int, dtype,
                      wi_packed4=False, wo_packed4=False):
    """Expert-compute for the local expert slice [e_loc] on local tokens.

    x2d [T, D]; eids/gates [T, k] global expert ids / combine weights;
    wi [e_loc, D, F'], wo [e_loc, F, D] (float or integer codes with
    per-channel scales — the quantized serving path).  Returns [T, D] (this
    shard's experts' contribution only — caller psums over "model").
    """
    t, _ = x2d.shape
    e_loc = wi.shape[0]
    xg, disp, slot_gate = _route_slots(x2d, eids, gates, e_loc, e_offset, cap)
    h = _expert_matmul(xg, wi, wi_scale, wi_packed4, dtype)
    if cfg.activation == "swiglu":
        gate_h, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate_h) * up
    else:
        h = layers.activate(h, cfg.activation)
    y = _expert_matmul(h, wo, wo_scale, wo_packed4, dtype)
    return _combine_slots(y, disp, slot_gate, t, x2d.dtype)


def _dispatch_compute_kneaded(x2d, eids, gates, kwi, kwo,
                              *, cfg: ModelConfig, e_offset, cap: int, dtype,
                              combine_dtype=None):
    """Kneaded expert-compute: per-expert SAC matmuls on the routed rows.

    ``kwi``/``kwo`` are per-layer expert banks — stacked
    :class:`~repro.core.kneading.KneadedWeight` with a leading local-expert
    axis ([e_loc, ...] arrays; scanning slices expert e's exact per-expert
    kneaded weight).  Instead of the capacity-padded [E, C, D] dense slab,
    each expert runs only its own gathered [cap, D] rows through
    ``matmul_any`` -> SAC: at decode cap <= 8, so this is the decode-GEMV
    fast path and the PR-9 activation-skip mask is computed from exactly
    the routed rows (unfilled capacity slots gather the zero pad row and
    contribute no K-tile presence — routing sparsity becomes skipped MXU
    passes for free).  Routing and combine are shared with the dense path
    (:func:`_route_slots` / :func:`_combine_slots`), so the f32 reduction
    order — and therefore bit-exactness of EP vs all-local through the
    psum — is unchanged.  ``combine_dtype`` overrides the combine output
    dtype: the EP shard function passes f32 so each shard's partial stays
    unrounded through the psum (a token's top-k experts can straddle
    shards — rounding per shard and again after the psum would double-round
    exactly those tokens; summing in f32 and rounding once after the psum
    reproduces the all-local reduction bit for bit).
    """
    t, _ = x2d.shape
    e_loc = kwi.planes.shape[0]
    if combine_dtype is None:
        combine_dtype = x2d.dtype
    xg, disp, slot_gate = _route_slots(x2d, eids, gates, e_loc, e_offset, cap)
    impl, skip = cfg.impl, cfg.activation_skip

    def expert_body(carry, ew):
        kwi_e, kwo_e, xg_e = ew
        h = matmul_any(xg_e, kwi_e, dtype, impl=impl, skip_activations=skip)
        if cfg.activation == "swiglu":
            gate_h, up = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(gate_h) * up
        else:
            h = layers.activate(h, cfg.activation)
        y_e = matmul_any(h, kwo_e, dtype, impl=impl, skip_activations=skip)
        return carry, y_e

    _, y = jax.lax.scan(expert_body, None, (kwi, kwo, xg))
    return _combine_slots(y, disp, slot_gate, t, combine_dtype)


def _moe_kneaded(h2, e2, g2, kwi, kwo, *, cfg: ModelConfig, mesh,
                 n_tokens: int, dtype):
    """Serve the kneaded expert banks, expert-parallel over "expert".

    The bank is sharded on the dedicated "expert" mesh axis when present
    (size > 1 and dividing E); the "model" axis keeps N-sharding the dense
    projections and simply replicates this computation.  Without an expert
    axis the identical dispatch runs with all experts local — the bit-exact
    oracle the EP acceptance tests compare against.
    """
    if mesh is None:
        # The serving engine installs its mesh via runtime.sharding's
        # threadlocal, not pspec.axis_rules — fall back so EP activates.
        from repro.runtime.sharding import current_serving_mesh
        mesh = current_serving_mesh()[0]
    e = cfg.num_experts
    cap = _capacity(n_tokens, cfg)
    ep = (mesh is not None and "expert" in mesh.axis_names
          and mesh.shape["expert"] > 1 and e % mesh.shape["expert"] == 0)
    if not ep:
        return _dispatch_compute_kneaded(h2, e2, g2, kwi, kwo, cfg=cfg,
                                         e_offset=0, cap=cap, dtype=dtype)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    e_loc = e // mesh.shape["expert"]

    def shard_fn(h_l, e_l, g_l, kwi_l, kwo_l):
        off = jax.lax.axis_index("expert") * e_loc
        # combine in f32 and round once after the psum: a token's top-k
        # experts can straddle expert shards, and per-shard rounding to the
        # activation dtype before the psum double-rounds those tokens vs
        # the all-local oracle
        y = _dispatch_compute_kneaded(h_l, e_l, g_l, kwi_l, kwo_l, cfg=cfg,
                                      e_offset=off, cap=cap, dtype=dtype,
                                      combine_dtype=jnp.float32)
        return jax.lax.psum(y, "expert").astype(h_l.dtype)

    # every bank array carries the (local) expert axis leading -> a uniform
    # P("expert") pytree spec shards dim 0 and replicates the rest
    bank_spec = jax.tree.map(lambda _: P("expert"), kwi)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(batch_axes, None), P(batch_axes, None),
                  P(batch_axes, None), bank_spec,
                  jax.tree.map(lambda _: P("expert"), kwo)),
        out_specs=P(batch_axes, None),
        check_vma=False,
    )(h2, e2, g2, kwi, kwo)


def moe_apply(p, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).  x: [B, S, D]."""
    dtype = jnp.dtype(cfg.dtype)
    b, s, d = x.shape
    # NB: no sp_gather here — the MoE shard_map's in_specs reshard the
    # tokens themselves; an explicit full-seq gather first was measured
    # 2.4x worse on arctic (EXPERIMENTS.md §Perf B5).
    h = layers.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    logits = matmul_any(h, p["router"], jnp.float32)     # [B, S, E] replicated
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss (computed on replicated routing).
    density = jnp.mean(
        jax.nn.one_hot(eids, cfg.num_experts, dtype=jnp.float32), axis=(0, 1, 2))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(density * mean_prob) * cfg.router_aux_coef

    h2, e2, g2 = (h.reshape(b * s, d), eids.reshape(b * s, -1),
                  gates.reshape(b * s, -1))
    mesh = pspec.current_mesh()
    from repro.core import routing_stats
    from repro.core.kneading import KneadedWeight
    routing_stats.record_routing(e2, cfg.num_experts,
                                 _capacity(b * s, cfg))
    if isinstance(p["wi"], KneadedWeight):
        y2 = _moe_kneaded(h2, e2, g2, p["wi"], p["wo"], cfg=cfg, mesh=mesh,
                          n_tokens=b * s, dtype=dtype)
        y = y2.reshape(b, s, d)
        if cfg.dense_residual:
            dense_h = layers.apply_norm(p["dense"]["ln"], x, cfg.norm,
                                        cfg.norm_eps)
            y = y + _ffn(dense_h, p["dense"], cfg.activation, dtype,
                         impl=cfg.impl, skip=cfg.activation_skip)
        return res_constrain(x + y.astype(x.dtype), cfg), aux
    wi_q, wi_s, wi_p4 = _split_quant(p["wi"])
    wo_q, wo_s, wo_p4 = _split_quant(p["wo"])
    ep_axes = [a for a in ("model",) if mesh is not None
               and a in mesh.axis_names and mesh.shape[a] > 1]
    if not ep_axes:
        cap = _capacity(b * s, cfg)
        y2 = _dispatch_compute(h2, e2, g2, wi_q, wi_s, wo_q, wo_s, cfg=cfg,
                               e_offset=0, cap=cap, dtype=dtype,
                               wi_packed4=wi_p4, wo_packed4=wo_p4)
    else:
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
        n_batch_shards = int(np.prod([mesh.shape[a] for a in batch_axes])) or 1
        t_loc = (b * s) // n_batch_shards
        e_shards = mesh.shape["model"]
        e_loc = cfg.num_experts // e_shards
        cap = _capacity(t_loc, cfg)
        # weights/scales enter shard_map EP-sharded on the expert axis
        zero = jnp.zeros((), dtype)
        wi_s_arg = wi_s if wi_s is not None else zero
        wo_s_arg = wo_s if wo_s is not None else zero
        escale_spec = (P("model", None, None) if wi_s is not None else P())

        def shard_fn(h_l, e_l, g_l, wi_l, wis_l, wo_l, wos_l):
            off = jax.lax.axis_index("model") * e_loc
            y = _dispatch_compute(
                h_l, e_l, g_l, wi_l,
                wis_l if wi_s is not None else None,
                wo_l, wos_l if wo_s is not None else None,
                cfg=cfg, e_offset=off, cap=cap, dtype=dtype,
                wi_packed4=wi_p4, wo_packed4=wo_p4)
            return jax.lax.psum(y, "model")

        y2 = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(batch_axes, None), P(batch_axes, None),
                      P(batch_axes, None), P("model", None, None),
                      escale_spec, P("model", None, None), escale_spec),
            out_specs=P(batch_axes, None),
            check_vma=False,
        )(h2, e2, g2, wi_q, wi_s_arg, wo_q, wo_s_arg)
    y = y2.reshape(b, s, d)
    if cfg.dense_residual:
        dense_h = layers.apply_norm(p["dense"]["ln"], x, cfg.norm,
                                    cfg.norm_eps)
        y = y + _ffn(dense_h, p["dense"], cfg.activation, dtype,
                     impl=cfg.impl, skip=cfg.activation_skip)
    out = res_constrain(x + y.astype(x.dtype), cfg)
    return out, aux
