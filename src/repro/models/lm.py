"""LanguageModel: one assembly covering all six assigned families.

  dense   — scan over (attn + mlp) layers                 (llama3, smollm,
                                                           phi3, nemotron)
  moe     — scan over (attn + moe [+ dense residual])     (arctic, qwen3-moe)
  vlm     — scan over groups of (gated cross-attn + k self layers)
                                                           (llama-3.2-vision)
  hybrid  — scan over mamba2 blocks, shared attn block every N
                                                           (zamba2)
  ssm     — scan over groups of (k mLSTM + 1 sLSTM)       (xlstm)
  encdec  — encoder self-attn stack + decoder w/ cross-attn
                                                           (whisper; conv
                                                            frontend stubbed)

Execution regimes: ``loss``/``logits`` (teacher forcing), ``prefill``
(returns KV/state caches), ``decode_step`` (one token).  All stacks scan over
layers with stacked params (HLO size O(1) in depth) and remat the scan body
when ``cfg.remat``.  Cross-entropy is computed in sequence chunks so the
[B, S, vocab] logits tensor never materializes (vocab up to 256k).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import blocks, layers, ssm

PyTree = object


def _split_keys(key, n):
    return list(jax.random.split(key, n))


def _stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn, prevent_cse=False) if cfg.remat else fn


def _write_rows(stores, news, pos):
    """Write each row r's new entries ``n[:, r]`` [L, 1, ...] into every
    cache leaf [L, B, S, ...] at sequence position ``pos[r]``: a loop over
    the rows, one dynamic_update_slice per leaf."""
    def row(r, stores):
        return tuple(jax.lax.dynamic_update_slice(
            c, jax.lax.dynamic_slice_in_dim(n, r, 1, axis=1),
            (0, r, pos[r]) + (0,) * (c.ndim - 3))
            for c, n in zip(stores, news))
    return jax.lax.fori_loop(0, pos.shape[0], row, stores)


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.family in ("dense", "moe", "vlm", "encdec"):
            assert cfg.num_heads % cfg.num_kv_heads == 0, cfg

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = _split_keys(key, 8)
        params: Dict = {
            "embed": layers.dense_init(keys[0], cfg.vocab_size, cfg.d_model),
            "final_norm": layers.norm_init(cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = layers.dense_init(
                keys[1], cfg.d_model, cfg.vocab_size)
        fam = cfg.family
        if fam in ("dense", "moe"):
            def layer_init(k):
                k1, k2 = jax.random.split(k)
                p = {"attn": blocks.attn_init(k1, cfg)}
                if fam == "moe":
                    p["moe"] = blocks.moe_init(k2, cfg)
                else:
                    p["mlp"] = blocks.mlp_init(k2, cfg)
                return p
            params["layers"] = _stack_init(layer_init, keys[2], cfg.num_layers)
        elif fam == "vlm":
            g = cfg.num_layers // cfg.cross_attn_every
            inner = cfg.cross_attn_every - 1

            def self_init(k):
                k1, k2 = jax.random.split(k)
                return {"attn": blocks.attn_init(k1, cfg),
                        "mlp": blocks.mlp_init(k2, cfg)}

            def group_init(k):
                k1, k2, k3 = jax.random.split(k, 3)
                return {
                    "cross": blocks.attn_init(k1, cfg, cross=True),
                    "cross_mlp": blocks.mlp_init(k2, cfg),
                    "cross_gate": jnp.zeros((), jnp.float32),
                    "selfs": _stack_init(self_init, k3, inner),
                }
            params["groups"] = _stack_init(group_init, keys[2], g)
        elif fam == "hybrid":
            params["layers"] = _stack_init(
                lambda k: ssm.mamba2_init(k, cfg), keys[2], cfg.num_layers)
            params["shared_attn"] = blocks.attn_init(keys[3], cfg)
            params["shared_mlp"] = blocks.mlp_init(keys[4], cfg)
        elif fam == "ssm":
            g = cfg.num_layers // cfg.slstm_every
            inner = cfg.slstm_every - 1

            def group_init(k):
                k1, k2 = jax.random.split(k)
                return {"mlstm": _stack_init(
                            lambda kk: ssm.mlstm_init(kk, cfg), k1, inner),
                        "slstm": ssm.slstm_init(k2, cfg)}
            params["groups"] = _stack_init(group_init, keys[2], g)
        elif fam == "encdec":
            def enc_init(k):
                k1, k2 = jax.random.split(k)
                return {"attn": blocks.attn_init(k1, cfg),
                        "mlp": blocks.mlp_init(k2, cfg)}

            def dec_init(k):
                k1, k2, k3 = jax.random.split(k, 3)
                return {"attn": blocks.attn_init(k1, cfg),
                        "cross": blocks.attn_init(k2, cfg, cross=True),
                        "mlp": blocks.mlp_init(k3, cfg)}
            params["encoder"] = {
                "layers": _stack_init(enc_init, keys[2], cfg.encoder_layers),
                "pos_embed": layers.dense_init(
                    keys[3], cfg.encoder_seq, cfg.d_model),
                "final_norm": layers.norm_init(cfg.d_model, cfg.norm),
            }
            params["layers"] = _stack_init(dec_init, keys[4], cfg.num_layers)
            params["dec_pos_embed"] = layers.dense_init(
                keys[5], 32_768, cfg.d_model)   # learned pos up to 32k ctx
        else:
            raise ValueError(fam)
        return params

    # ------------------------------------------------------- full-seq trunk
    def _embed(self, params, tokens):
        cfg = self.cfg
        h = jnp.take(params["embed"], tokens, axis=0)
        h = h.astype(jnp.dtype(cfg.dtype))
        from repro.models.blocks import res_constrain
        return res_constrain(h, cfg)

    def _encode(self, params, frames):
        """Whisper encoder over precomputed conv-frontend frames (stub)."""
        cfg = self.cfg
        h = frames.astype(jnp.dtype(cfg.dtype))
        h = h + params["encoder"]["pos_embed"][None, :h.shape[1]].astype(h.dtype)

        def body(carry, p_l):
            y, _ = blocks.attn_apply(p_l["attn"], carry, cfg,
                                     positions=None, causal=False)
            y = blocks.mlp_apply(p_l["mlp"], y, cfg)
            return y, None

        body = _maybe_remat(body, cfg)
        h, _ = jax.lax.scan(body, h, params["encoder"]["layers"])
        return layers.apply_norm(params["encoder"]["final_norm"], h, cfg.norm)

    def _trunk(self, params, h, positions, *, collect_cache: bool,
               cross_src: Optional[jax.Array] = None):
        """Full-sequence pass.  Returns (h, aux_loss, cache_or_None)."""
        cfg = self.cfg
        fam = cfg.family
        aux0 = jnp.zeros((), jnp.float32)

        if fam in ("dense", "moe"):
            def body(carry, p_l):
                h, aux = carry
                h, kv = blocks.attn_apply(p_l["attn"], h, cfg,
                                          positions=positions,
                                          return_kv=collect_cache)
                if fam == "moe":
                    h, a = blocks.moe_apply(p_l["moe"], h, cfg)
                    aux = aux + a
                else:
                    h = blocks.mlp_apply(p_l["mlp"], h, cfg)
                return (h, aux), kv
            body = _maybe_remat(body, cfg)
            (h, aux), kvs = jax.lax.scan(body, (h, aux0), params["layers"])
            return h, aux, ({"k": kvs[0], "v": kvs[1]} if collect_cache else None)

        if fam == "vlm":
            def group_body(carry, p_g):
                h, aux = carry
                y, ckv = blocks.attn_apply(p_g["cross"], h, cfg,
                                           positions=positions, causal=False,
                                           kv_src=cross_src,
                                           return_kv=collect_cache)
                gate = jnp.tanh(p_g["cross_gate"])
                h = (h.astype(jnp.float32)
                     + gate * (y - h).astype(jnp.float32)).astype(h.dtype)
                h = blocks.mlp_apply(p_g["cross_mlp"], h, cfg)

                def self_body(carry2, p_l):
                    h2, aux2 = carry2
                    h2, kv = blocks.attn_apply(p_l["attn"], h2, cfg,
                                               positions=positions,
                                               return_kv=collect_cache)
                    h2 = blocks.mlp_apply(p_l["mlp"], h2, cfg)
                    return (h2, aux2), kv
                (h, aux), kvs = jax.lax.scan(self_body, (h, aux),
                                             p_g["selfs"])
                return (h, aux), (ckv, kvs)
            group_body = _maybe_remat(group_body, cfg)
            (h, aux), (ckvs, kvss) = jax.lax.scan(group_body, (h, aux0),
                                                  params["groups"])
            cache = None
            if collect_cache:
                cache = {"cross_k": ckvs[0], "cross_v": ckvs[1],
                         "k": kvss[0], "v": kvss[1]}
            return h, aux, cache

        if fam == "hybrid":
            n_apps = int(np.ceil(cfg.num_layers / cfg.attn_every))

            def body(carry, xs):
                h, aux, kv_store = carry
                p_l, idx = xs
                is_attn = (idx % cfg.attn_every) == 0
                kvh, hd = cfg.num_kv_heads, cfg.hd
                zero_kv = jnp.zeros(h.shape[:2] + (kvh, hd),
                                    jnp.dtype(cfg.dtype))

                def attn_branch(h):
                    y, kv = blocks.attn_apply(
                        params["shared_attn"], h, cfg, positions=positions,
                        return_kv=True)
                    y = blocks.mlp_apply(params["shared_mlp"], y, cfg)
                    return y, kv

                def skip_branch(h):
                    return h, (zero_kv, zero_kv)

                # cond (not select): the shared block really is skipped on
                # non-attention layers — no wasted FLOPs in the compiled HLO.
                h, kv = jax.lax.cond(is_attn, attn_branch, skip_branch, h)
                if collect_cache:
                    app = idx // cfg.attn_every
                    ks_, vs_ = kv_store
                    ks_ = jnp.where(is_attn, ks_.at[app].set(kv[0]), ks_)
                    vs_ = jnp.where(is_attn, vs_.at[app].set(kv[1]), vs_)
                    kv_store = (ks_, vs_)
                h, (conv_st, ssm_st) = ssm.mamba2_apply(p_l, h, cfg)
                ys = (conv_st, ssm_st) if collect_cache else None
                return (h, aux, kv_store), ys
            b_sz, s_len = h.shape[0], h.shape[1]
            kv0 = None
            if collect_cache:
                kvh, hd = cfg.num_kv_heads, cfg.hd
                kv0 = (jnp.zeros((n_apps, b_sz, s_len, kvh, hd),
                                 jnp.dtype(cfg.dtype)),
                       jnp.zeros((n_apps, b_sz, s_len, kvh, hd),
                                 jnp.dtype(cfg.dtype)))
            body = _maybe_remat(body, cfg)
            (h, aux, kv0), states = jax.lax.scan(
                body, (h, aux0, kv0),
                (params["layers"], jnp.arange(cfg.num_layers)))
            cache = None
            if collect_cache:
                cache = {"k": kv0[0], "v": kv0[1],
                         "conv": states[0], "ssm": states[1]}
            return h, aux, cache

        if fam == "ssm":
            def group_body(carry, p_g):
                h, aux = carry

                def m_body(h2, p_l):
                    h2, st = ssm.mlstm_apply(p_l, h2, cfg)
                    return h2, st
                h, m_states = jax.lax.scan(m_body, h, p_g["mlstm"])
                h, s_state = ssm.slstm_apply(p_g["slstm"], h, cfg)
                return (h, aux), (m_states, s_state)
            group_body = _maybe_remat(group_body, cfg)
            (h, aux), states = jax.lax.scan(group_body, (h, aux0),
                                            params["groups"])
            cache = None
            if collect_cache:
                cache = {"mlstm": states[0], "slstm": states[1]}
            return h, aux, cache

        if fam == "encdec":
            def body(carry, p_l):
                h, aux = carry
                h, kv = blocks.attn_apply(p_l["attn"], h, cfg,
                                          positions=positions,
                                          return_kv=collect_cache)
                hc, ckv = blocks.attn_apply(p_l["cross"], h, cfg,
                                            positions=positions, causal=False,
                                            kv_src=cross_src,
                                            return_kv=collect_cache)
                h = hc
                h = blocks.mlp_apply(p_l["mlp"], h, cfg)
                return (h, aux), (kv, ckv)
            body = _maybe_remat(body, cfg)
            (h, aux), (kvs, ckvs) = jax.lax.scan(body, (h, aux0),
                                                 params["layers"])
            cache = None
            if collect_cache:
                cache = {"k": kvs[0], "v": kvs[1],
                         "cross_k": ckvs[0], "cross_v": ckvs[1]}
            return h, aux, cache

        raise ValueError(fam)

    # ------------------------------------------------------------- logits
    def _positions(self, tokens):
        b, s = tokens.shape
        return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def _hidden(self, params, batch, collect_cache=False):
        cfg = self.cfg
        tokens = batch["tokens"]
        h = self._embed(params, tokens)
        positions = self._positions(tokens)
        cross_src = None
        if cfg.family == "encdec":
            cross_src = self._encode(params, batch["frames"])
            h = h + params["dec_pos_embed"][None, :h.shape[1]].astype(h.dtype)
        elif cfg.family == "vlm":
            cross_src = batch["image_embeds"].astype(jnp.dtype(cfg.dtype))
        h, aux, cache = self._trunk(params, h, positions,
                                    collect_cache=collect_cache,
                                    cross_src=cross_src)
        h = layers.apply_norm(params["final_norm"], h, cfg.norm)
        return h, aux, cache

    def _unembed_w(self, params):
        return (params["embed"].T if self.cfg.tie_embeddings
                else params["unembed"])

    def logits(self, params, batch) -> jax.Array:
        h, _, _ = self._hidden(params, batch)
        return layers.matmul_any(h, self._unembed_w(params),
                                 jnp.dtype(self.cfg.dtype),
                                 impl=self.cfg.impl,
                                 skip_activations=self.cfg.activation_skip)

    def loss(self, params, batch, loss_chunk: int = 0) -> jax.Array:
        """Cross entropy + MoE aux.  The vocab matmul runs in bf16 with f32
        softmax statistics.  Unchunked by default: the [tokens, V] logits are
        modest per device under both profiles (tp: V is model-sharded; dp:
        per-device tokens are small), and chunking via lax.scan forces a
        per-chunk f32 all-reduce of the unembed gradient (measured +14 GiB
        per device per step on llama3 — §Perf iteration log).  Pass
        ``loss_chunk`` > 0 for the memory-constrained chunked path."""
        cfg = self.cfg
        h, aux, _ = self._hidden(params, batch)
        labels = batch["labels"]
        b, s, d = h.shape
        w = self._unembed_w(params)

        def ce(hc, lc):
            logits = layers.matmul_any(hc, w, jnp.dtype(cfg.dtype))
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None],
                                       axis=-1)[..., 0]
            return jnp.sum(logz - gold)

        if not loss_chunk or s % loss_chunk:
            return ce(h, labels) / (b * s) + aux
        c = loss_chunk
        h_ch = jnp.moveaxis(h.reshape(b, s // c, c, d), 1, 0)
        l_ch = jnp.moveaxis(labels.reshape(b, s // c, c), 1, 0)
        total, _ = jax.lax.scan(
            lambda acc, xs: (acc + ce(*xs), None),
            jnp.zeros((), jnp.float32), (h_ch, l_ch))
        return total / (b * s) + aux

    # ------------------------------------------------------------- serving
    def prefill(self, params, batch) -> Tuple[jax.Array, PyTree]:
        """Returns (last-token logits [B, V], cache)."""
        h, _, cache = self._hidden(params, batch, collect_cache=True)
        if (self.cfg.kv_cache_bits == 8
                and self.cfg.family in ("dense", "moe")):
            k8, ks = layers.quantize_kv(cache["k"])
            v8, vs = layers.quantize_kv(cache["v"])
            cache = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
        last = h[:, -1]
        logits = layers.matmul_any(last, self._unembed_w(params),
                                   jnp.dtype(self.cfg.dtype),
                                   impl=self.cfg.impl,
                                   skip_activations=self.cfg.activation_skip)
        # pad KV caches to max length happens in inference.engine; here the
        # cache covers the prefilled prefix exactly.
        return logits, cache

    def cache_spec(self, batch: int, max_len: int) -> PyTree:
        """ShapeDtypeStructs of the decode cache (dry-run input stand-ins)."""
        cfg = self.cfg
        fam = cfg.family
        dt = jnp.dtype(cfg.dtype)
        kvh, hd, L = cfg.num_kv_heads, cfg.hd, cfg.num_layers
        kv = lambda n, s: jax.ShapeDtypeStruct((n, batch, s, kvh, hd), dt)
        if fam in ("dense", "moe"):
            if cfg.kv_cache_bits == 8:
                kv8 = lambda n, s: jax.ShapeDtypeStruct(
                    (n, batch, s, kvh, hd), jnp.int8)
                sc = lambda n, s: jax.ShapeDtypeStruct(
                    (n, batch, s, kvh), jnp.float32)
                return {"k": kv8(L, max_len), "v": kv8(L, max_len),
                        "k_scale": sc(L, max_len), "v_scale": sc(L, max_len)}
            return {"k": kv(L, max_len), "v": kv(L, max_len)}
        if fam == "vlm":
            g = L // cfg.cross_attn_every
            inner = cfg.cross_attn_every - 1
            kv_self = jax.ShapeDtypeStruct(
                (g, inner, batch, max_len, kvh, hd), dt)
            kv_cross = jax.ShapeDtypeStruct(
                (g, batch, cfg.num_image_tokens, kvh, hd), dt)
            return {"k": kv_self, "v": kv_self,
                    "cross_k": kv_cross, "cross_v": kv_cross}
        if fam == "encdec":
            enc = jax.ShapeDtypeStruct(
                (L, batch, cfg.encoder_seq, kvh, hd), dt)
            return {"k": kv(L, max_len), "v": kv(L, max_len),
                    "cross_k": enc, "cross_v": enc}
        if fam == "hybrid":
            n_apps = int(np.ceil(L / cfg.attn_every))
            conv, state = ssm.mamba2_cache_spec(cfg, batch)
            stack = lambda sds, n: jax.ShapeDtypeStruct((n,) + sds.shape,
                                                        sds.dtype)
            return {"k": kv(n_apps, max_len), "v": kv(n_apps, max_len),
                    "conv": stack(conv, L), "ssm": stack(state, L)}
        if fam == "ssm":
            g = L // cfg.slstm_every
            inner = cfg.slstm_every - 1
            m = ssm.mlstm_cache_spec(cfg, batch)
            s = ssm.slstm_cache_spec(cfg, batch)
            stack2 = lambda sds: jax.ShapeDtypeStruct((g, inner) + sds.shape,
                                                      sds.dtype)
            stack1 = lambda sds: jax.ShapeDtypeStruct((g,) + sds.shape,
                                                      sds.dtype)
            return {"mlstm": stack2(m), "slstm": tuple(stack1(x) for x in s)}
        raise ValueError(fam)

    def decode_step(self, params, token, pos, cache):
        """One token: token [B, 1], pos [B] (index of the new token).

        Returns (logits [B, V], updated cache)."""
        cfg = self.cfg
        fam = cfg.family
        h = self._embed(params, token)
        if fam == "encdec":
            h = h + jnp.take(params["dec_pos_embed"], pos, axis=0)[:, None]

        # The decode scans below (but the unsharded dense/moe one) keep the
        # big caches in the scan CARRY and update them with
        # dynamic_update_slice on the (unsharded) stack axis.  Passing caches
        # as xs AND ys instead would double-buffer them (input stack +
        # collected output stack) — measured +9.6 GiB/device on nemotron
        # decode_32k.  Read-only caches (cross-attn KV) stay xs.
        def _upd(store, new, *idx):
            new = new.astype(store.dtype)
            return jax.lax.dynamic_update_slice(
                store, new[(None,) * len(idx)], idx + (0,) * new.ndim)

        if fam in ("dense", "moe"):
            names = (("k", "v", "k_scale", "v_scale") if "k_scale" in cache
                     else ("k", "v"))
            store = tuple(cache[n] for n in names)
            aux0 = jnp.zeros((), jnp.float32)

            def layer(h, aux, p_l, slices, return_kv):
                h, kv = blocks.attn_apply(p_l["attn"], h, cfg, positions=None,
                                          cache=slices, pos=pos,
                                          return_kv=return_kv)
                if fam == "moe":
                    h, a = blocks.moe_apply(p_l["moe"], h, cfg)
                    aux = aux + a
                else:
                    h = blocks.mlp_apply(p_l["mlp"], h, cfg)
                return h, aux, kv

            if blocks.cache_seq_sharded():
                # each layer writes its slice by masked select, the stack
                # stays in the carry
                def body(carry, xs):
                    h, aux, store = carry
                    p_l, idx = xs
                    slices = tuple(
                        jax.lax.dynamic_index_in_dim(c, idx, 0, False)
                        for c in store)
                    h, aux, slices = layer(h, aux, p_l, slices, False)
                    return (h, aux, tuple(_upd(c, n, idx)
                                          for c, n in zip(store, slices))), None
                (h, _, store), _ = jax.lax.scan(
                    body, (h, aux0, store),
                    (params["layers"], jnp.arange(cfg.num_layers)))
            else:
                # The layers read the cache as xs, read-only, and return the
                # new token's entries [L, B, 1, ...] as ys; each row's are
                # then written at (row, pos[row]), in place on a donated
                # cache: B small updates a leaf, no whole-cache rewrite.  A
                # loop over rows, not B unrolled updates: it ran ~0.8 ms a
                # step faster on a v5e at smollm-360m's widths, 64 rows.
                def body(carry, xs):
                    h, aux = carry
                    p_l, slices = xs
                    h, aux, new = layer(h, aux, p_l, slices, True)
                    return (h, aux), new
                (h, _), new = jax.lax.scan(body, (h, aux0),
                                           (params["layers"], store))
                store = _write_rows(store, new, pos)
            cache = dict(zip(names, store))
        elif fam == "vlm":
            inner = cfg.cross_attn_every - 1

            def group_body(carry, xs):
                h, k_all, v_all = carry
                p_g, ck, cv, g_idx = xs
                y, _ = blocks.attn_apply(p_g["cross"], h, cfg,
                                         positions=pos[:, None],
                                         causal=False, kv_const=(ck, cv))
                gate = jnp.tanh(p_g["cross_gate"])
                h = (h.astype(jnp.float32)
                     + gate * (y - h).astype(jnp.float32)).astype(h.dtype)
                h = blocks.mlp_apply(p_g["cross_mlp"], h, cfg)

                def self_body(carry2, xs2):
                    h2, k_all, v_all = carry2
                    p_l, i_idx = xs2
                    kc = jax.lax.dynamic_index_in_dim(
                        jax.lax.dynamic_index_in_dim(k_all, g_idx, 0, False),
                        i_idx, 0, False)
                    vc = jax.lax.dynamic_index_in_dim(
                        jax.lax.dynamic_index_in_dim(v_all, g_idx, 0, False),
                        i_idx, 0, False)
                    h2, (kc, vc) = blocks.attn_apply(
                        p_l["attn"], h2, cfg, positions=None,
                        cache=(kc, vc), pos=pos)
                    h2 = blocks.mlp_apply(p_l["mlp"], h2, cfg)
                    return (h2, _upd(k_all, kc, g_idx, i_idx),
                            _upd(v_all, vc, g_idx, i_idx)), None
                (h, k_all, v_all), _ = jax.lax.scan(
                    self_body, (h, k_all, v_all),
                    (p_g["selfs"], jnp.arange(inner)))
                return (h, k_all, v_all), None
            (h, k_new, v_new), _ = jax.lax.scan(
                group_body, (h, cache["k"], cache["v"]),
                (params["groups"], cache["cross_k"], cache["cross_v"],
                 jnp.arange(cfg.num_layers // cfg.cross_attn_every)))
            cache = dict(cache, k=k_new, v=v_new)
        elif fam == "encdec":
            def body(carry, xs):
                h, k_all, v_all = carry
                p_l, ck, cv, idx = xs
                kc = jax.lax.dynamic_index_in_dim(k_all, idx, 0, False)
                vc = jax.lax.dynamic_index_in_dim(v_all, idx, 0, False)
                h, (kc, vc) = blocks.attn_apply(p_l["attn"], h, cfg,
                                                positions=None,
                                                cache=(kc, vc), pos=pos)
                h, _ = blocks.attn_apply(p_l["cross"], h, cfg,
                                         positions=pos[:, None], causal=False,
                                         kv_const=(ck, cv))
                h = blocks.mlp_apply(p_l["mlp"], h, cfg)
                return (h, _upd(k_all, kc, idx), _upd(v_all, vc, idx)), None
            (h, k_new, v_new), _ = jax.lax.scan(
                body, (h, cache["k"], cache["v"]),
                (params["layers"], cache["cross_k"], cache["cross_v"],
                 jnp.arange(cfg.num_layers)))
            cache = dict(cache, k=k_new, v=v_new)
        elif fam == "hybrid":
            def body(carry, xs):
                h, ks_, vs_, conv_all, ssm_all = carry
                p_l, idx = xs
                is_attn = (idx % cfg.attn_every) == 0
                app = idx // cfg.attn_every
                kc = jax.lax.dynamic_index_in_dim(ks_, app, 0, keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(vs_, app, 0, keepdims=False)

                def attn_branch(args):
                    h, kc, vc = args
                    y, (kc2, vc2) = blocks.attn_apply(
                        params["shared_attn"], h, cfg, positions=None,
                        cache=(kc, vc), pos=pos)
                    y = blocks.mlp_apply(params["shared_mlp"], y, cfg)
                    return y, kc2, vc2

                h, kc2, vc2 = jax.lax.cond(
                    is_attn, attn_branch, lambda a: a, (h, kc, vc))
                ks_ = _upd(ks_, kc2, app)
                vs_ = _upd(vs_, vc2, app)
                conv_c = jax.lax.dynamic_index_in_dim(conv_all, idx, 0, False)
                ssm_c = jax.lax.dynamic_index_in_dim(ssm_all, idx, 0, False)
                h, (conv_c, ssm_c) = ssm.mamba2_apply(
                    p_l, h, cfg, cache=(conv_c, ssm_c))
                return (h, ks_, vs_, _upd(conv_all, conv_c, idx),
                        _upd(ssm_all, ssm_c, idx)), None
            (h, k_new, v_new, conv_new, ssm_new), _ = jax.lax.scan(
                body, (h, cache["k"], cache["v"], cache["conv"],
                       cache["ssm"]),
                (params["layers"], jnp.arange(cfg.num_layers)))
            cache = {"k": k_new, "v": v_new, "conv": conv_new,
                     "ssm": ssm_new}
        elif fam == "ssm":
            def group_body(carry, xs):
                h, m_all, s_all = carry
                p_g, g_idx = xs

                def m_body(carry2, xs2):
                    h2, m_all = carry2
                    p_l, i_idx = xs2
                    st = jax.lax.dynamic_index_in_dim(
                        jax.lax.dynamic_index_in_dim(m_all, g_idx, 0, False),
                        i_idx, 0, False)
                    h2, st2 = ssm.mlstm_apply(p_l, h2, cfg, cache=st)
                    return (h2, _upd(m_all, st2, g_idx, i_idx)), None
                (h, m_all), _ = jax.lax.scan(
                    m_body, (h, m_all),
                    (p_g["mlstm"], jnp.arange(cfg.slstm_every - 1)))
                s_st = tuple(
                    jax.lax.dynamic_index_in_dim(s, g_idx, 0, False)
                    for s in s_all)
                h, s_new = ssm.slstm_apply(p_g["slstm"], h, cfg, cache=s_st)
                s_all = tuple(_upd(s, n, g_idx)
                              for s, n in zip(s_all, s_new))
                return (h, m_all, s_all), None
            (h, m_new, s_new), _ = jax.lax.scan(
                group_body, (h, cache["mlstm"], cache["slstm"]),
                (params["groups"],
                 jnp.arange(cfg.num_layers // cfg.slstm_every)))
            cache = {"mlstm": m_new, "slstm": s_new}
        else:
            raise ValueError(fam)

        h = layers.apply_norm(params["final_norm"], h, cfg.norm)
        logits = layers.matmul_any(h[:, 0], self._unembed_w(params),
                                   jnp.dtype(cfg.dtype),
                                   impl=cfg.impl,
                                   skip_activations=cfg.activation_skip)
        return logits, cache
