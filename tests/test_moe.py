"""Kneaded expert-parallel MoE serving (docs/DESIGN.md §13).

Covers the expert-bank kneading form ([L, E, K, N] leaves kneaded
per-expert with independent schedules), the routed decode-GEMV path
(planes == pallas bit-exact through the whole qwen3-moe smoke engine),
expert parallelism over the dedicated "expert" mesh axis (EP ∈ {2, 4} and
the 2-D expert×model mesh bit-identical to a clean 1-device all-local
oracle subprocess), and the routing semantics the paths share:

* top_k tie-break order is pinned (``jax.lax.top_k`` keeps the LOWER
  expert index on equal probabilities) — routing must not depend on an
  unspecified sort,
* capacity overflow drops by global arrival order (capacity_factor < 1
  keeps the first ``cap`` routed tokens per expert, zeroes the rest),
* the Switch aux-loss value is pinned against an independent numpy
  recompute on a fixed seed,
* per-step routed/dropped counters surface through ``latency_stats()``
  and the static per-(layer, expert) work tables through
  ``expert_work_table()``.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config
from repro.core import routing_stats
from repro.core.kneading import (KNEADABLE_NAMES, KneadedWeight,
                                 knead_padded, knead_stacked)
from repro.inference.engine import ServingConfig, ServingEngine, knead_params
from repro.models import blocks
from repro.models.lm import LanguageModel

MIN_DIM = 8      # smoke dims are tiny; knead every projection

MOE_ARCH = "qwen3-moe-30b-a3b"


@pytest.fixture(scope="module")
def moe():
    """qwen3-moe smoke arch + float params + kneaded params."""
    cfg = get_config(MOE_ARCH, smoke=True)
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kparams = knead_params(params, bits=8, min_dim=MIN_DIM, kneaded=True)
    return cfg, model, params, kparams


# ------------------------------------------------------- expert-bank form

def test_knead_stacked_expert_bank_structure():
    """[L, E, K, N] kneads to a bank whose (l, e) slice equals the
    independent 2-D knead of w[l, e] exactly, with the work dim padded to
    the cross-slice max by repeating each tile's last K-tile with an empty
    plane mask."""
    key = jax.random.split(jax.random.PRNGKey(3), 2)
    w = jax.random.normal(key[0], (2, 3, 96, 128)) * 0.05
    keep = jax.random.uniform(key[1], w.shape) >= 0.7
    w = w * keep
    bank = knead_stacked(w, bits=8)
    assert bank.planes.shape[:2] == (2, 3)
    assert bank.schedule.counts.shape[:2] == (2, 3)
    solos = [[knead_padded(w[l, e], bits=8) for e in range(3)]
             for l in range(2)]
    assert bank.schedule.num_work == max(
        s.schedule.num_work for row in solos for s in row)
    assert bank.schedule.total_work == sum(
        s.schedule.total_work for row in solos for s in row)
    for l in range(2):
        for e in range(3):
            solo = solos[l][e]
            np.testing.assert_array_equal(np.asarray(bank.planes[l, e]),
                                          np.asarray(solo.planes))
            np.testing.assert_array_equal(np.asarray(bank.signs[l, e]),
                                          np.asarray(solo.signs))
            np.testing.assert_array_equal(np.asarray(bank.scale[l, e]),
                                          np.asarray(solo.scale))
            np.testing.assert_array_equal(
                np.asarray(bank.schedule.counts[l, e]),
                np.asarray(solo.schedule.counts))
            width = solo.schedule.num_work
            np.testing.assert_array_equal(
                np.asarray(bank.schedule.plane_bits[l, e, :, :width]),
                np.asarray(solo.schedule.plane_bits))
            np.testing.assert_array_equal(
                np.asarray(bank.schedule.ktile_ids[l, e, :, :width]),
                np.asarray(solo.schedule.ktile_ids))
            kid = np.asarray(bank.schedule.ktile_ids[l, e])
            assert (kid[:, width:] == kid[:, width - 1:width]).all()
            assert (np.asarray(bank.schedule.plane_bits[l, e, :, width:])
                    == 0).all()


def test_expert_bank_work_table():
    """work_table() sums each slice's MXU passes — a [L, E] static load
    map whose total equals the schedule's total_work."""
    w = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 64, 128)) * 0.05
    bank = knead_stacked(w, bits=8)
    table = bank.work_table()
    assert table.shape == (2, 4)
    assert table.sum() == bank.schedule.total_work
    for l in range(2):
        for e in range(4):
            solo = knead_padded(w[l, e], bits=8)
            assert table[l, e] == solo.schedule.total_work


def test_expert_bank_rejects_n_sharding():
    """Banks place on the 'expert' axis, never through the N-sharder."""
    w = jax.random.normal(jax.random.PRNGKey(5), (2, 2, 64, 128)) * 0.05
    bank = knead_stacked(w, bits=8)
    with pytest.raises(ValueError, match="expert"):
        bank.shard(None, "model")


def test_pallas_kernel_rejects_unsliced_bank():
    """The 2-D kernel entry refuses a stacked bank loudly instead of
    walking garbage."""
    from repro.kernels.sac_matmul.ops import sac_matmul_pallas
    w = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 64, 128)) * 0.05
    bank = knead_stacked(w, bits=8)
    with pytest.raises(ValueError, match="stacked"):
        sac_matmul_pallas(jnp.ones((1, 64)), bank)


def test_kneadable_names_single_definition():
    """Satellite: the engine and the launch specs read the SAME tuple —
    the two serving paths cannot drift on what gets kneaded."""
    from repro.inference import engine
    from repro.launch import specs
    assert engine._KNEADABLE is KNEADABLE_NAMES
    assert specs._KNEADABLE is KNEADABLE_NAMES


def test_knead_params_warns_on_unkneaded_leaves(moe, caplog):
    """Kneadable-name leaves below min_dim are named in one warning
    instead of silently serving float."""
    _, _, params, _ = moe
    import logging
    with caplog.at_level(logging.WARNING, logger="repro.inference.engine"):
        knead_params(params, bits=8, min_dim=4096, kneaded=True)
    msgs = [r.getMessage() for r in caplog.records
            if "un-kneaded" in r.getMessage()]
    assert len(msgs) == 1
    assert "wq" in msgs[0] and "moe/wi" in msgs[0].replace("'", "")


def test_knead_params_builds_expert_banks(moe):
    """The >2-stack-dim exclusion is lifted: [L, E, K, N] MoE leaves
    become KneadedWeight banks with both stack axes in front."""
    cfg, _, params, kparams = moe
    for name in ("wi", "wo"):
        kw = kparams["layers"]["moe"][name]
        orig = params["layers"]["moe"][name]
        assert isinstance(kw, KneadedWeight), name
        assert kw.planes.shape[:2] == (cfg.num_layers, cfg.num_experts)
        assert kw.schedule.counts.shape[:2] == (cfg.num_layers,
                                                cfg.num_experts)
        assert (kw.logical_k, kw.logical_n) == orig.shape[-2:]
    # the router stays float: tiny and not a projection suffix
    assert not isinstance(kparams["layers"]["moe"]["router"], KneadedWeight)


# ------------------------------------------------------ routing semantics

def test_top_k_tie_break_prefers_lower_expert():
    """Pinned tie-break: equal router probabilities route to the LOWEST
    expert index, at every k — the decode trace is reproducible across
    runs and machines or this fails."""
    probs = jnp.full((1, 1, 6), 1.0 / 6.0)
    _, eids = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(np.asarray(eids)[0, 0], [0, 1, 2])
    # partial tie under a strict maximum: order is still index-ascending
    probs = jnp.asarray([[[0.1, 0.3, 0.1, 0.3, 0.2, 0.0]]])
    _, eids = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(np.asarray(eids)[0, 0], [1, 3, 4])


def test_capacity_overflow_drops_by_arrival_order():
    """capacity_factor < 1: each expert keeps its first ``cap`` routed
    tokens in arrival order; overflow tokens contribute exactly zero."""
    cfg = ModelConfig(name="tiny-moe", family="moe", num_experts=2,
                      top_k=1, moe_dff=16, d_model=8,
                      capacity_factor=0.5)
    t, d = 8, 8
    x2d = jnp.ones((t, d))
    eids = jnp.zeros((t, 1), jnp.int32)        # every token -> expert 0
    gates = jnp.ones((t, 1), jnp.float32)
    cap = blocks._capacity(t, cfg)
    assert cap < t
    xg, disp, slot_gate = blocks._route_slots(x2d, eids, gates, 2, 0, cap)
    # expert 0's slots hold tokens 0..cap-1 (arrival order), expert 1 none
    np.testing.assert_array_equal(np.asarray(disp[:cap]), np.arange(cap))
    assert (np.asarray(disp[cap:]) == t).all()           # pad-row gathers
    assert np.asarray(slot_gate[:cap]).sum() == cap
    assert np.asarray(slot_gate[cap:]).sum() == 0.0
    # the combine zeroes dropped tokens: scatter y == slot outputs back
    y = jnp.ones((2, cap, d))
    out = blocks._combine_slots(y, disp, slot_gate, t, jnp.float32)
    np.testing.assert_array_equal(np.asarray(out[:cap]), np.ones((cap, d)))
    np.testing.assert_array_equal(np.asarray(out[cap:]),
                                  np.zeros((t - cap, d)))


def test_router_aux_loss_pinned_on_fixed_seed(moe):
    """The Switch aux-loss value on a fixed seed equals an independent
    numpy recompute of E * sum(density * mean_prob) * coef."""
    cfg, model, params, _ = moe
    toks = jax.random.randint(jax.random.PRNGKey(11), (2, 8), 0,
                              cfg.vocab_size)
    x = jnp.take(params["embed"], toks, axis=0).astype(cfg.dtype)
    p0 = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    _, aux = blocks.moe_apply(p0, x, cfg)

    from repro.models import layers as L
    from repro.models.layers import matmul_any
    h = L.apply_norm(p0["ln"], x, cfg.norm)
    logits = matmul_any(h, p0["router"], jnp.float32)
    probs = np.asarray(jax.nn.softmax(logits.astype(jnp.float32), axis=-1))
    _, eids = jax.lax.top_k(jnp.asarray(probs), cfg.top_k)
    eids = np.asarray(eids)
    density = np.stack([(eids == e).mean() for e in range(cfg.num_experts)])
    expected = (cfg.num_experts * (density * probs.mean((0, 1))).sum()
                * cfg.router_aux_coef)
    np.testing.assert_allclose(float(aux), expected, rtol=1e-4)
    assert float(aux) > 0.0


def test_moe_config_validation():
    with pytest.raises(ValueError, match="top_k"):
        ModelConfig(family="moe", num_experts=4, top_k=8, moe_dff=16)
    with pytest.raises(ValueError, match="moe_dff"):
        ModelConfig(family="moe", num_experts=4, top_k=2, moe_dff=0,
                    d_ff=0)
    with pytest.raises(ValueError, match="capacity_factor"):
        ModelConfig(family="moe", num_experts=4, top_k=2, moe_dff=16,
                    capacity_factor=0.0)


# ------------------------------------------------- kneaded decode parity

def test_moe_engine_pallas_bit_exact_vs_planes(moe):
    """ACCEPTANCE: kneaded-expert decode through the routed per-expert
    GEMV path is bit-exact planes == pallas on the qwen3-moe smoke
    engine, prefill logits and 32-token greedy generations."""
    cfg, _, params, _ = moe
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, 8), 0,
                              cfg.vocab_size)
    outs = {}
    for impl in ("planes", "pallas"):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=48, impl=impl,
                                          knead_min_dim=MIN_DIM))
        with eng._mesh_ctx():
            logits, _ = eng._prefill(eng.params, {"tokens": toks})
        outs[impl] = (np.asarray(logits.astype(jnp.float32)),
                      np.asarray(eng.generate({"tokens": toks}, 32)))
    np.testing.assert_array_equal(outs["pallas"][0], outs["planes"][0])
    np.testing.assert_array_equal(outs["pallas"][1], outs["planes"][1])


def test_moe_engine_activation_skip_bit_exact(moe):
    """Two-sided skip on the routed per-expert GEMV calls (the PR-9 mask
    computed from exactly the routed rows) changes nothing bitwise."""
    cfg, _, params, _ = moe
    toks = jax.random.randint(jax.random.PRNGKey(13), (2, 8), 0,
                              cfg.vocab_size)
    gens = {}
    for skip in (False, True):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=48, impl="pallas",
                                          knead_min_dim=MIN_DIM,
                                          activation_skip=skip))
        gens[skip] = np.asarray(eng.generate({"tokens": toks}, 16))
    np.testing.assert_array_equal(gens[True], gens[False])


def test_moe_engine_quant_serves_dense_slab(moe):
    """The quantized (non-kneaded) MoE serving path is untouched: it still
    runs the capacity-padded dense slab and decodes."""
    cfg, _, params, _ = moe
    toks = jax.random.randint(jax.random.PRNGKey(14), (2, 8), 0,
                              cfg.vocab_size)
    eng = ServingEngine(cfg, params,
                        ServingConfig(max_len=32, impl="quant",
                                      quant_bits=8, knead_min_dim=MIN_DIM))
    out = eng.generate({"tokens": toks}, 8)
    assert out.shape == (2, 8)


# ------------------------------------------------- routing-load stats

def test_routing_stats_surface_through_latency_stats(moe):
    """Per-step routed-token and capacity-drop counters reach
    latency_stats(); the static work table is [L, E] per bank."""
    cfg, _, params, _ = moe
    routing_stats.reset_routing_stats()
    eng = ServingEngine(cfg, params,
                        ServingConfig(max_len=32, impl="pallas",
                                      knead_min_dim=MIN_DIM))
    eng.generate({"tokens": jnp.zeros((2, 8), jnp.int32)}, 4)
    stats = eng.latency_stats()
    assert stats["routing_steps"] > 0
    # every (token, k) routed pair lands somewhere: routed + dropped
    # accounts for batch * top_k per routed call
    assert stats["routed_tokens"] > 0
    assert stats["capacity_dropped"] >= 0
    tables = eng.expert_work_table()
    assert set(tables) == {"layers/moe/wi", "layers/moe/wo"}
    for table in tables.values():
        assert table.shape == (cfg.num_layers, cfg.num_experts)
        assert (table >= 0).all() and table.sum() > 0


def test_non_moe_engine_reports_no_routing_stats():
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        ServingConfig(max_len=32, impl="pallas",
                                      knead_min_dim=MIN_DIM))
    eng.generate({"tokens": jnp.zeros((2, 8), jnp.int32)}, 4)
    stats = eng.latency_stats()
    assert "routed_tokens" not in stats
    assert eng.expert_work_table() == {}


# ------------------------------------------- expert-parallel validation

def test_engine_expert_shards_validation(moe):
    cfg, _, params, _ = moe
    with pytest.raises(ValueError, match="does not knead"):
        ServingEngine(cfg, params, ServingConfig(expert_shards=2,
                                                 impl="quant"))
    with pytest.raises(ValueError, match="not divisible"):
        ServingEngine(cfg, params, ServingConfig(expert_shards=3,
                                                 impl="pallas",
                                                 knead_min_dim=MIN_DIM))
    dense = get_config("smollm-360m", smoke=True)
    dparams = LanguageModel(dense).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="MoE"):
        ServingEngine(dense, dparams, ServingConfig(expert_shards=2,
                                                    impl="pallas"))


# ------------------------------- EP vs all-local subprocess oracle

_ENGINE_RUN = textwrap.dedent("""
    import json, sys
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.core import routing_stats
    from repro.inference.engine import ServingConfig, ServingEngine

    from repro.models.lm import LanguageModel

    expert_shards = int(sys.argv[2])
    model_shards = int(sys.argv[3])
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_len=48, impl="pallas", knead_min_dim=8,
        expert_shards=expert_shards, shards=model_shards))
    with eng._mesh_ctx():
        logits, _ = eng._prefill(eng.params, {"tokens": toks})
    gen = eng.generate({"tokens": toks}, 32)
    np.save(sys.argv[1] + "_logits.npy",
            np.asarray(logits.astype(np.float32)))
    np.save(sys.argv[1] + "_gen.npy", np.asarray(gen))
    stats = eng.latency_stats()
    meta = {"devices": jax.device_count(),
            "routed": stats.get("routed_tokens", 0),
            "work": {k: v.tolist()
                     for k, v in eng.expert_work_table().items()}}
    print(json.dumps(meta))
""")


def _run(code, out_prefix, expert_shards, model_shards, extra_env):
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH",
                                                       "/usr/bin:/bin")}
    env.update(extra_env)
    res = subprocess.run([sys.executable, "-c", code, out_prefix,
                          str(expert_shards), str(model_shards)],
                         capture_output=True, text=True, env=env,
                         cwd=".", timeout=1200)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def oracle_run(tmp_path_factory):
    """The clean single-device all-experts-local engine run, computed once
    for every EP parametrization."""
    prefix = str(tmp_path_factory.mktemp("moe_oracle") / "oracle")
    meta = _run(_ENGINE_RUN, prefix, 0, 0, {"JAX_PLATFORMS": "cpu"})
    return prefix, meta


@pytest.mark.parametrize("expert_shards,model_shards",
                         [(2, 0), (4, 0), (2, 2)])
def test_expert_sharded_engine_bit_exact_vs_all_local_oracle(
        expert_shards, model_shards, tmp_path, oracle_run):
    """ACCEPTANCE: the expert-sharded engine (EP ∈ {2, 4}, plus the 2-D
    expert×model mesh) on forced host devices produces qwen3-moe prefill
    logits AND 32-token greedy generations bit-identical to the all-local
    single-device oracle — same slot routing, same f32 scatter-add combine
    pairing, psum over "expert" only adds exact zeros from non-owning
    shards."""
    oracle_prefix, oracle_meta = oracle_run
    n_force = int(os.environ.get("REPRO_SHARD_TEST_DEVICES", "4"))
    meta = _run(
        _ENGINE_RUN, str(tmp_path / "ep"), expert_shards, model_shards,
        {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_force}",
         "JAX_PLATFORMS": "cpu"})
    assert meta["devices"] == n_force
    assert oracle_meta["devices"] == 1
    np.testing.assert_array_equal(
        np.load(tmp_path / "ep_logits.npy"),
        np.load(oracle_prefix + "_logits.npy"))
    np.testing.assert_array_equal(
        np.load(tmp_path / "ep_gen.npy"),
        np.load(oracle_prefix + "_gen.npy"))
    # routing counters and static work tables agree with the oracle's —
    # placement must not change what routes where
    assert meta["routed"] == oracle_meta["routed"]
    assert meta["work"] == oracle_meta["work"]
