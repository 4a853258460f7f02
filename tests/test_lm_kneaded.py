"""Kneaded LM serving (decode-GEMV path): parity, cache round-trip, engine.

The transformer serving stack runs every ``_KNEADABLE`` projection through
the kneaded bit-plane form: stacked [L, K, N] scan-layer weights kneaded per
layer with a leading schedule axis (``knead_stacked``), dispatched by
``cfg.impl`` through ``sac_matmul`` — impl="pallas" being the
schedule-compacted kernel's decode-GEMV fast path.  "planes" replays the
same accumulation order, so whole-model prefill logits, decode-step logits,
and 32-token greedy generations are asserted BIT-EXACT between the two
(the acceptance criterion), with the float model as the quantization-error
reference.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import parity
import pytest

from repro.configs.registry import get_config
from repro.core.kneading import KneadedWeight, knead_padded, knead_stacked
from repro.inference.engine import ServingConfig, ServingEngine, knead_params
from repro.models.lm import LanguageModel

MIN_DIM = 8      # smoke dims are tiny; knead every projection


@pytest.fixture(scope="module")
def smol():
    """smollm-360m smoke arch + float params + kneaded params."""
    cfg = get_config("smollm-360m", smoke=True)
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kparams = knead_params(params, bits=8, min_dim=MIN_DIM, kneaded=True)
    return cfg, model, params, kparams


def _model(cfg, impl):
    return LanguageModel(dataclasses.replace(cfg, impl=impl))


def _pad_cache(cache, cur, to):
    def pad(x):
        if x.ndim >= 4 and x.shape[-3] == cur:
            p = [(0, 0)] * x.ndim
            p[-3] = (0, to - cur)
            return jnp.pad(x, p)
        return x
    return jax.tree.map(pad, cache)


# --------------------------------------------------- stacked kneading form

def test_knead_params_stacks_scan_layers(smol):
    """Every attention/MLP projection leaf becomes a KneadedWeight whose
    arrays carry a leading num_layers axis (the scan slice axis)."""
    cfg, _, params, kparams = smol
    layers = kparams["layers"]
    for block, names in (("attn", ("wq", "wk", "wv", "wo")),
                         ("mlp", ("wi_gate", "wi_up", "wo"))):
        for name in names:
            kw = layers[block][name]
            orig = params["layers"][block][name]
            assert isinstance(kw, KneadedWeight), (block, name)
            L = cfg.num_layers
            assert kw.planes.shape[0] == L
            assert kw.signs.shape[0] == L
            assert kw.schedule.counts.shape[0] == L
            assert kw.schedule.plane_bits.shape == (
                L, kw.schedule.n_tiles, kw.schedule.num_work)
            assert (kw.logical_k, kw.logical_n) == orig.shape[-2:]
    # embeddings/norms stay float (tied smollm has no unembed leaf)
    assert not isinstance(kparams["embed"], KneadedWeight)


def test_stacked_layer_schedules_independent(smol):
    """The stacked kneading invariant: layer l's planes/signs/scale and
    compacted schedule equal exactly ``knead_padded(w[l])``'s — per-layer
    schedules are built independently, and the work-dim padding to the
    cross-layer max repeats each tile's last K-tile with an empty plane
    mask."""
    cfg, _, params, _ = smol
    w = params["layers"]["attn"]["wq"]             # [L, K, N]
    stacked = knead_stacked(w, bits=8)
    for layer in range(cfg.num_layers):
        solo = knead_padded(w[layer], bits=8)
        np.testing.assert_array_equal(np.asarray(stacked.planes[layer]),
                                      np.asarray(solo.planes))
        np.testing.assert_array_equal(np.asarray(stacked.signs[layer]),
                                      np.asarray(solo.signs))
        np.testing.assert_array_equal(np.asarray(stacked.scale[layer]),
                                      np.asarray(solo.scale))
        np.testing.assert_array_equal(
            np.asarray(stacked.schedule.counts[layer]),
            np.asarray(solo.schedule.counts))
        W = solo.schedule.num_work
        np.testing.assert_array_equal(
            np.asarray(stacked.schedule.plane_bits[layer, :, :W]),
            np.asarray(solo.schedule.plane_bits))
        np.testing.assert_array_equal(
            np.asarray(stacked.schedule.ktile_ids[layer, :, :W]),
            np.asarray(solo.schedule.ktile_ids))
        # padding columns repeat the last K-tile of each tile's list and
        # run no plane
        pbits = np.asarray(stacked.schedule.plane_bits[layer])
        kid = np.asarray(stacked.schedule.ktile_ids[layer])
        assert (pbits[:, W:] == 0).all()
        assert (kid[:, W:] == kid[:, W - 1:W]).all()
    assert stacked.schedule.num_work == max(
        knead_padded(w[i], bits=8).schedule.num_work
        for i in range(cfg.num_layers))
    assert stacked.schedule.total_work == sum(
        knead_padded(w[i], bits=8).schedule.total_work
        for i in range(cfg.num_layers))


# LM projection-shaped sweep of the shared harness (hypothesis-gated)
test_lm_impl_parity_sweep = parity.make_sweep_test(
    shapes=((1, 960, 960), (1, 960, 2560), (7, 2560, 960)), bits=(8,),
    sparsities=(0.0, 0.9))


# --------------------------------------------------------- model parity

def test_prefill_and_decode_step_parity(smol):
    """One decode step through the whole kneaded model: pallas bit-exact vs
    the planes oracle, and within quantization error of the float model."""
    cfg, model, params, kparams = smol
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    lp, cache_p = jax.jit(_model(cfg, "planes").prefill)(kparams, batch)
    lg, cache_g = jax.jit(_model(cfg, "pallas").prefill)(kparams, batch)
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lg))
    for a, b in zip(jax.tree.leaves(cache_p), jax.tree.leaves(cache_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    pos = jnp.full((2,), 8, jnp.int32)
    dp, _ = jax.jit(_model(cfg, "planes").decode_step)(
        kparams, toks[:, :1], pos, _pad_cache(cache_p, 8, 16))
    dg, _ = jax.jit(_model(cfg, "pallas").decode_step)(
        kparams, toks[:, :1], pos, _pad_cache(cache_g, 8, 16))
    np.testing.assert_array_equal(np.asarray(dp), np.asarray(dg))

    # float reference: int8 kneading drifts logits only within quant error
    lf = model.logits(params, batch)[:, -1].astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(lp.astype(jnp.float32) - lf))
                / (jnp.max(jnp.abs(lf)) + 1e-9))
    assert rel < 0.12


def test_prefill_decode_cache_roundtrip(smol):
    """Prefill -> padded cache -> decode must agree with the full forward
    at the decoded position (the KV cache round-trip), on the kneaded
    pallas path."""
    cfg, _, _, kparams = smol
    model = _model(cfg, "pallas")
    S = 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, S + 1), 0,
                              cfg.vocab_size)
    full = jax.jit(model.logits)(kparams, {"tokens": toks})
    _, cache = jax.jit(model.prefill)(kparams, {"tokens": toks[:, :S]})
    dec, cache2 = jax.jit(model.decode_step)(
        kparams, toks[:, S:S + 1], jnp.full((2,), S, jnp.int32),
        _pad_cache(cache, S, S + 4))
    ref = full[:, -1].astype(jnp.float32)
    err = float(jnp.max(jnp.abs(dec.astype(jnp.float32) - ref)))
    assert err / (float(jnp.max(jnp.abs(ref))) + 1e-9) < 0.05
    # the round trip extends the cache in place: seq extent is preserved
    assert cache2["k"].shape == _pad_cache(cache, S, S + 4)["k"].shape


# ------------------------------------------------------------- engine e2e

def test_serving_engine_pallas_bit_exact_vs_planes(smol):
    """Acceptance: ServingEngine greedy decode with impl="pallas" on
    smollm-360m (smoke dims) is bit-exact against the planes oracle for
    >= 32 tokens."""
    cfg, _, params, _ = smol
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                              cfg.vocab_size)
    gens = {}
    for impl in ("planes", "pallas"):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=48, impl=impl,
                                          knead_min_dim=MIN_DIM))
        gens[impl] = eng.generate({"tokens": toks}, 32)
    assert gens["pallas"].shape == (2, 32)
    np.testing.assert_array_equal(np.asarray(gens["pallas"]),
                                  np.asarray(gens["planes"]))


@pytest.mark.parametrize("impl", ["planes", "pallas"])
def test_serving_engine_activation_skip_bit_exact(smol, impl):
    """Acceptance (docs/DESIGN.md §12): 32-token greedy decode with
    ``activation_skip=True`` is BIT-IDENTICAL to skip-off on both the
    planes oracle and the pallas kernel — the runtime activation-occupancy
    intersection only drops tile-dots whose contribution is exactly 0 and
    preserves the ascending K accumulation order of the survivors."""
    from repro.core import activation_occupancy

    cfg, _, params, _ = smol
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                              cfg.vocab_size)
    gens = {}
    activation_occupancy.reset_skip_stats()
    for skip in (False, True):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=48, impl=impl,
                                          knead_min_dim=MIN_DIM,
                                          activation_skip=skip))
        gens[skip] = eng.generate({"tokens": toks}, 32)
        if skip and impl == "pallas":
            # skip stats surface through the request front end
            stats = eng.latency_stats()
            if "act_skip_frac" in stats:
                assert 0.0 <= stats["act_skip_frac"] <= 1.0
                assert (stats["executed_tile_dots"]
                        <= stats["weight_tile_dots"])
    assert gens[True].shape == (2, 32)
    np.testing.assert_array_equal(np.asarray(gens[True]),
                                  np.asarray(gens[False]))
    if impl == "pallas":
        # the masked kernel actually ran (decode-GEMV rows engage the gate)
        stats = activation_occupancy.skip_stats()
        assert stats["weight_tile_dots"] > 0
        assert stats["executed_tile_dots"] <= stats["weight_tile_dots"]


def test_serving_engine_kneaded_close_to_float(smol):
    """Kneaded greedy decode mostly matches bf16 greedy decode (int8
    quantization changes at most occasional argmax ties)."""
    cfg, _, params, _ = smol
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                              cfg.vocab_size)
    eng_f = ServingEngine(cfg, params, ServingConfig(max_len=32))
    eng_k = ServingEngine(cfg, params,
                          ServingConfig(max_len=32, impl="pallas",
                                        knead_min_dim=MIN_DIM))
    g_f = eng_f.generate({"tokens": toks}, 16)
    g_k = eng_k.generate({"tokens": toks}, 16)
    agree = float(jnp.mean((g_f == g_k).astype(jnp.float32)))
    assert agree > 0.6


def test_serving_engine_ssm_family_kneaded_parity():
    """SSM-family projections (in_proj/up/down/w_in/w_out/...) dispatch
    through cfg.impl too — xlstm greedy decode is bit-exact planes vs
    pallas, so the impl switch cannot silently fall back to the default
    path for non-attention blocks."""
    cfg = get_config("xlstm-1.3b", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                              cfg.vocab_size)
    gens = {}
    for impl in ("planes", "pallas"):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=32, impl=impl,
                                          knead_min_dim=MIN_DIM))
        gens[impl] = eng.generate({"tokens": toks}, 8)
    np.testing.assert_array_equal(np.asarray(gens["pallas"]),
                                  np.asarray(gens["planes"]))


def test_serving_engine_impl_validation(smol):
    cfg, _, params, _ = smol
    with pytest.raises(ValueError, match="impl"):
        ServingEngine(cfg, params, ServingConfig(impl="mxu"))


# ------------------------------------- zamba2 hybrid decode regression

@pytest.fixture(scope="module")
def zamba():
    cfg = get_config("zamba2-2.7b", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0,
                              cfg.vocab_size)
    return cfg, params, toks


@pytest.mark.parametrize("impl", ["float", "quant", "int", "planes",
                                  "pallas"])
def test_zamba2_hybrid_engine_decodes(zamba, impl):
    """Regression (ROADMAP seed bug): ServingEngine decode on the hybrid
    family must run on EVERY serving path.  The engine's cache pad used to
    sniff shapes — any >=4-dim cache whose -3 axis equalled the prompt
    length got padded to max_len, and the Mamba2 SSM state [L, B, H, p, n]
    collides whenever its head count equals the prompt length (smoke: both
    8), stretching the state's *head* axis and crashing ``ssm.ssd_step``.
    The pad is now keyed on the cache dict's names ("k"/"v"/"*_scale"
    only), so SSM/conv states pass through untouched."""
    cfg, params, toks = zamba
    scfg = ServingConfig(max_len=32, impl=impl, knead_min_dim=MIN_DIM,
                         quant_bits=8 if impl == "quant" else 0)
    eng = ServingEngine(cfg, params, scfg)
    out = eng.generate({"tokens": toks}, 4)
    assert out.shape == (2, 4)
    assert out.dtype == jnp.int32


def test_zamba2_hybrid_decode_pallas_matches_planes(zamba):
    """The hybrid family's kneaded decode is bit-exact pallas vs planes —
    the SSM in_proj/out_proj projections dispatch through the SAC paths
    just like attention does."""
    cfg, params, toks = zamba
    gens = {}
    for impl in ("planes", "pallas"):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=32, impl=impl,
                                          knead_min_dim=MIN_DIM))
        gens[impl] = eng.generate({"tokens": toks}, 8)
    np.testing.assert_array_equal(np.asarray(gens["pallas"]),
                                  np.asarray(gens["planes"]))


def test_pad_cache_leaves_ssm_state_heads_alone(zamba):
    """The head-count == prompt-length collision, pinned directly: after
    _pad_cache only the attention KV seq axes grow; conv/ssm states keep
    their shapes bit-for-bit."""
    cfg, params, toks = zamba
    eng = ServingEngine(cfg, params, ServingConfig(max_len=32))
    logits, cache = eng._prefill(eng.params, {"tokens": toks})
    padded = eng._pad_cache(cache, toks.shape[1])
    assert padded["k"].shape[-3] == 32
    assert padded["v"].shape[-3] == 32
    np.testing.assert_array_equal(np.asarray(padded["conv"]),
                                  np.asarray(cache["conv"]))
    np.testing.assert_array_equal(np.asarray(padded["ssm"]),
                                  np.asarray(cache["ssm"]))


# ------------------------------------- batched request front end (LM)

def test_engine_submit_drain_matches_batch_generate(smol):
    """drain() serves queued prompts in padding-bucket micro-batches whose
    outputs equal generate() on the same padded batch bitwise (same shape
    -> same XLA program -> identical greedy argmax)."""
    cfg, _, params, _ = smol
    eng = ServingEngine(cfg, params,
                        ServingConfig(max_len=32, impl="pallas",
                                      knead_min_dim=MIN_DIM, buckets=(4,)))
    toks = jax.random.randint(jax.random.PRNGKey(7), (3, 8), 0,
                              cfg.vocab_size)
    ids = [eng.submit(toks[i], num_tokens=6) for i in range(3)]
    res = eng.drain()
    assert sorted(res) == sorted(ids)
    ref = eng.generate(
        {"tokens": jnp.pad(toks, ((0, 1), (0, 0)))}, 6)
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(res[rid]),
                                      np.asarray(ref[i]))
    stats = eng.latency_stats()
    assert stats["requests"] == 3
    assert stats["p95_ms"] >= stats["p50_ms"] > 0
    assert stats["mean_batch_fill"] == pytest.approx(0.75)
    assert eng.drain() == {}                 # queue fully drained


def test_engine_drain_groups_by_prompt_length(smol):
    """Mixed prompt lengths drain in per-length micro-batches (positions
    stay exact — no prompt padding), each bitwise-equal to generate() at
    its own padded shape; per-request token budgets are honored."""
    cfg, _, params, _ = smol
    eng = ServingEngine(cfg, params,
                        ServingConfig(max_len=32, impl="int",
                                      knead_min_dim=MIN_DIM, buckets=(2,)))
    short = jax.random.randint(jax.random.PRNGKey(8), (2, 4), 0,
                               cfg.vocab_size)
    long = jax.random.randint(jax.random.PRNGKey(9), (1, 10), 0,
                              cfg.vocab_size)
    rid_s = [eng.submit(short[i], num_tokens=5) for i in range(2)]
    rid_l = eng.submit(long[0], num_tokens=3)
    res = eng.drain()
    ref_s = eng.generate({"tokens": short}, 5)
    ref_l = eng.generate({"tokens": jnp.pad(long, ((0, 1), (0, 0)))}, 3)
    for i, rid in enumerate(rid_s):
        assert res[rid].shape == (5,)
        np.testing.assert_array_equal(np.asarray(res[rid]),
                                      np.asarray(ref_s[i]))
    assert res[rid_l].shape == (3,)
    np.testing.assert_array_equal(np.asarray(res[rid_l]),
                                  np.asarray(ref_l[0]))
    log = list(eng._request_log)
    assert sorted(r["prompt_len"] for r in log) == [4, 4, 10]


def test_engine_submit_validation(smol):
    cfg, _, params, _ = smol
    eng = ServingEngine(cfg, params, ServingConfig(max_len=16))
    with pytest.raises(ValueError, match="one prompt"):
        eng.submit(jnp.zeros((2, 8), jnp.int32))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(jnp.zeros((8,), jnp.int32), num_tokens=16)
    with pytest.raises(ValueError, match="buckets"):
        ServingEngine(cfg, params, ServingConfig(buckets=(4, 2)))
