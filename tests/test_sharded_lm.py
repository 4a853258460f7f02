"""Mesh-sharded kneaded LM serving (docs/DESIGN.md §8).

Covers the stacked schedule partition end to end: ``shard_stacked_schedule``
structure (per-layer slab equality against the single-layer sharder,
N-tiles that don't divide the shard count, all-empty shards), bit-exact
parity of the scan-sliced sharded matmul against the unsharded stacked
kernel, the engine validation surface, and the acceptance criterion — a
ServingEngine with ``shards ∈ {2, 4}`` on forced host devices producing
smollm-360m prefill logits and 32-token greedy generations bit-identical
to the unsharded single-device engine.

Oracle note (same as tests/test_sharded.py): forcing host devices perturbs
XLA CPU threading for large dense matmuls, so multi-device runs compare
against a clean 1-device subprocess.  At smoke-LM dims the dense ops
between the kneaded matmuls are small enough to be threading-stable, which
is what lets the cross-process comparison stay *bitwise* rather than
allclose (verified empirically; a future arch whose smoke dims drift
should fall back to comparing generations plus tight-tolerance logits).
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

from repro.core.kneading import knead_padded, knead_stacked
from repro.core.sac import sac_matmul
from repro.core.schedule import (ShardedStackedKneadedWeight, shard_schedule,
                                 shard_stacked_schedule)
from repro.inference.engine import ServingConfig, ServingEngine, knead_params
from repro.models.lm import LanguageModel


def _stacked_w(seed, layers, k, n, sparsity=0.0):
    kk = jax.random.split(jax.random.PRNGKey(seed), 2)
    w = jax.random.normal(kk[0], (layers, k, n)) * 0.05
    if sparsity > 0:
        keep = jax.random.uniform(kk[1], w.shape) >= sparsity
        w = w * keep
    return w


def _scan_matmul(a, stacked_kw):
    """Run a through every layer of a stacked (possibly sharded) kneaded
    weight via lax.scan — the model's slicing pattern."""
    def body(carry, kw_l):
        return carry, sac_matmul(a, kw_l, impl="pallas")
    _, outs = jax.lax.scan(body, 0, stacked_kw)
    return outs                                      # [L, M, N]


# ------------------------------------------------------------- structure

def test_shard_stacked_matches_per_layer_shard():
    """Layer l of the stacked sharded weight holds exactly the slabs
    shard_schedule(knead_padded(w[l])) builds, up to the work-dim padding
    to the cross-layer max; per-layer work rows partition each layer's
    unsharded total."""
    w = _stacked_w(0, 3, 300, 384, sparsity=0.6)
    stacked = knead_stacked(w, bits=8)
    ssk = shard_stacked_schedule(stacked, 2)
    assert isinstance(ssk, ShardedStackedKneadedWeight)
    assert ssk.num_layers == 3 and ssk.num_shards == 2
    for layer in range(3):
        solo = shard_schedule(knead_padded(w[layer], bits=8), 2)
        np.testing.assert_array_equal(np.asarray(ssk.planes[layer]),
                                      np.asarray(solo.planes))
        np.testing.assert_array_equal(np.asarray(ssk.signs[layer]),
                                      np.asarray(solo.signs))
        np.testing.assert_array_equal(np.asarray(ssk.scale[layer]),
                                      np.asarray(solo.scale))
        np.testing.assert_array_equal(np.asarray(ssk.counts[layer]),
                                      np.asarray(solo.counts))
        width = solo.num_work      # stacked pads work to the cross-layer max
        np.testing.assert_array_equal(
            np.asarray(ssk.plane_bits[layer][..., :width]),
            np.asarray(solo.plane_bits))
        np.testing.assert_array_equal(
            np.asarray(ssk.ktile_ids[layer][..., :width]),
            np.asarray(solo.ktile_ids))
        assert ssk.layer_shard_work[layer] == solo.shard_work
        assert sum(ssk.layer_shard_work[layer]) == \
            knead_padded(w[layer], bits=8).schedule.total_work
    assert ssk.shard_work == tuple(
        sum(row[s] for row in ssk.layer_shard_work) for s in range(2))
    assert ssk.total_work == stacked.schedule.total_work


def test_shard_stacked_indivisible_tiles():
    """3 N-tiles over 2 shards: one all-empty padding tile appended on every
    layer; parity stays bit-exact after the logical-N slice."""
    w = _stacked_w(1, 2, 512, 384)               # 3 N-tiles
    stacked = knead_stacked(w, bits=8)
    ssk = shard_stacked_schedule(stacked, 2)
    assert ssk.tiles_per_shard == 2 and ssk.n == 512   # 3 -> 4 tiles
    assert ssk.logical_n == 384
    assert ssk.total_work == stacked.schedule.total_work
    a = jax.random.normal(jax.random.PRNGKey(2), (8, 512))
    out = _scan_matmul(a, ssk)
    ref = _scan_matmul(a, stacked)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_shard_stacked_empty_shard():
    """Layers whose upper output columns are all zero put zero work on the
    second shard of every layer; outputs stay bit-exact and the per-layer
    imbalance report pins the skew."""
    w = _stacked_w(3, 2, 512, 512).at[:, :, 256:].set(0.0)
    stacked = knead_stacked(w, bits=8)
    ssk = shard_stacked_schedule(stacked, 2)
    for layer in range(2):
        assert ssk.layer_shard_work[layer][1] == 0
        assert ssk.layer_shard_work[layer][0] > 0
        assert ssk.layer_imbalance(layer)["imbalance"] == pytest.approx(2.0)
    assert ssk.imbalance()["max_layer_imbalance"] == pytest.approx(2.0)
    a = jax.random.normal(jax.random.PRNGKey(4), (8, 512))
    out = _scan_matmul(a, ssk)
    np.testing.assert_array_equal(
        np.asarray(out[:, :, 256:]), np.zeros((2, 8, 256), np.float32))
    ref = _scan_matmul(a, stacked)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------------- serial parity

@pytest.mark.parametrize("shards", [1, 2, 4])
def test_scan_sliced_sharded_matmul_bit_exact(shards):
    """The serial shard walk of every scan-sliced layer is bit-exact
    against the unsharded stacked kernel — prefill (M=8) and decode-GEMV
    (M=1) regimes both."""
    w = _stacked_w(5, 3, 512, 512, sparsity=0.7)
    stacked = knead_stacked(w, bits=8)
    ssk = shard_stacked_schedule(stacked, shards)
    for m in (1, 8):
        a = jax.random.normal(jax.random.PRNGKey(6 + m), (m, 512))
        out = _scan_matmul(a, ssk)
        ref = _scan_matmul(a, stacked)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sharded_weight_requires_pallas_impl():
    w = _stacked_w(7, 2, 512, 256)
    ssk = shard_stacked_schedule(knead_stacked(w, bits=8), 2)

    def run(carry, kw_l):
        return carry, sac_matmul(jnp.ones((1, 512)), kw_l, impl="planes")

    with pytest.raises(ValueError, match="Pallas kernel only"):
        jax.lax.scan(run, 0, ssk)


def test_stacked_sharded_must_be_sliced():
    """The full [L, S, ...] weight cannot hit the matmul un-sliced."""
    w = _stacked_w(8, 2, 512, 256)
    ssk = shard_stacked_schedule(knead_stacked(w, bits=8), 2)
    with pytest.raises(ValueError, match="un-sliced"):
        sac_matmul(jnp.ones((1, 512)), ssk, impl="pallas")


def test_shard_stacked_rejects_unstacked():
    kw = knead_padded(jax.random.normal(jax.random.PRNGKey(9), (512, 256)))
    with pytest.raises(ValueError, match="stacked"):
        shard_stacked_schedule(kw, 2)


def test_shard_stacked_balanced_per_layer_repartition():
    """Balanced stacked sharding repartitions each layer independently:
    two layers dense in *opposite* column halves both reach imbalance 1.0,
    each through its own row of the tile->shard permutation table, and the
    scan-sliced matmul stays bit-exact against the unsharded stack."""
    w = _stacked_w(10, 2, 512, 512)
    w = w.at[0, :, 256:].set(0.0).at[1, :, :256].set(0.0)
    stacked = knead_stacked(w, bits=8)
    cont = shard_stacked_schedule(stacked, 2)
    assert cont.imbalance()["max_layer_imbalance"] == pytest.approx(2.0)
    bal = shard_stacked_schedule(stacked, 2, partition="balanced")
    assert bal.tile_slot.shape == (2, 4)
    for layer in range(2):
        row = np.asarray(bal.tile_slot[layer])
        assert sorted(row.tolist()) == [0, 1, 2, 3]        # bijection
        assert bal.layer_imbalance(layer)["imbalance"] == pytest.approx(1.0)
    assert bal.imbalance()["max_layer_imbalance"] == pytest.approx(1.0)
    # the layers genuinely got different permutations
    assert not np.array_equal(np.asarray(bal.tile_slot[0]),
                              np.asarray(bal.tile_slot[1]))
    a = jax.random.normal(jax.random.PRNGKey(11), (8, 512))
    np.testing.assert_array_equal(np.asarray(_scan_matmul(a, bal)),
                                  np.asarray(_scan_matmul(a, stacked)))


# ------------------------------------------------------ engine validation

def test_engine_sharded_requires_pallas():
    from repro.configs.registry import get_config
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="single-device only"):
        ServingEngine(cfg, params,
                      ServingConfig(impl="int", shards=2, knead_min_dim=8))


def test_knead_params_shards_every_kneadable_leaf():
    from repro.configs.registry import get_config
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    kparams = knead_params(params, bits=8, min_dim=8, kneaded=True, shards=2)
    layers = kparams["layers"]
    for block, names in (("attn", ("wq", "wk", "wv", "wo")),
                         ("mlp", ("wi_gate", "wi_up", "wo"))):
        for name in names:
            leaf = layers[block][name]
            assert isinstance(leaf, ShardedStackedKneadedWeight), (block, name)
            assert leaf.num_layers == cfg.num_layers
            assert leaf.num_shards == 2
            assert leaf.planes.shape[:2] == (cfg.num_layers, 2)


# ------------------------------------------- multi-device acceptance test

_ENGINE_RUN = textwrap.dedent("""
    import json, sys
    import jax, numpy as np
    from repro.configs.registry import get_config
    from repro.inference.engine import ServingConfig, ServingEngine
    from repro.models.lm import LanguageModel

    shards = int(sys.argv[2])
    partition = sys.argv[3]
    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_len=48, impl="pallas", knead_min_dim=8, shards=shards,
        shard_partition=partition))
    with eng._mesh_ctx():
        logits, _ = eng._prefill(eng.params, {"tokens": toks})
    gen = eng.generate({"tokens": toks}, 32)
    np.save(sys.argv[1] + "_logits.npy",
            np.asarray(logits.astype(np.float32)))
    np.save(sys.argv[1] + "_gen.npy", np.asarray(gen))
    meta = {"devices": jax.device_count()}
    if shards > 1:
        leaf = eng.params["layers"]["attn"]["wq"]
        rep = leaf.imbalance()
        meta["wq_shard_work"] = rep["shard_work"]
        meta["wq_max_layer_imbalance"] = rep["max_layer_imbalance"]
    print(json.dumps(meta))
""")


def _run(code, out_prefix, shards, extra_env, partition="contiguous"):
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH",
                                                       "/usr/bin:/bin")}
    env.update(extra_env)
    res = subprocess.run([sys.executable, "-c", code, out_prefix,
                          str(shards), partition],
                         capture_output=True, text=True, env=env,
                         cwd=".", timeout=1200)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def oracle_run(tmp_path_factory):
    """The clean single-device unsharded engine run, computed ONCE for the
    whole shards parametrization (the oracle command is identical for
    every shard count)."""
    prefix = str(tmp_path_factory.mktemp("lm_oracle") / "oracle")
    meta = _run(_ENGINE_RUN, prefix, 0, {"JAX_PLATFORMS": "cpu"})
    return prefix, meta


@pytest.mark.parametrize("partition", ["contiguous", "balanced"])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_lm_engine_bit_exact_vs_single_device_oracle(
        shards, partition, tmp_path, oracle_run):
    """ACCEPTANCE: ServingEngine with every kneaded projection's schedule
    sharded over forced host devices (shard_map-launched SAC kernels inside
    the layer scans) produces smollm-360m prefill logits AND 32-token
    greedy generations bit-identical to the unsharded engine on a clean
    single device — under both tile->shard partitionings.  Smoke dims pad
    every projection to a single N-tile, so "balanced" degenerates to the
    same placement (one tile can't be split); the point of the balanced leg
    is that the permutation-gather epilogue is exercised end to end through
    the full engine and changes nothing."""
    oracle_prefix, oracle_meta = oracle_run
    n_force = int(os.environ.get("REPRO_SHARD_TEST_DEVICES", "4"))
    sharded_meta = _run(
        _ENGINE_RUN, str(tmp_path / "sharded"), shards,
        {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_force}",
         "JAX_PLATFORMS": "cpu"}, partition=partition)
    assert sharded_meta["devices"] == n_force
    assert oracle_meta["devices"] == 1
    np.testing.assert_array_equal(
        np.load(tmp_path / "sharded_logits.npy"),
        np.load(oracle_prefix + "_logits.npy"))
    np.testing.assert_array_equal(
        np.load(tmp_path / "sharded_gen.npy"),
        np.load(oracle_prefix + "_gen.npy"))
    # static load accounting survived the trip through the engine: smoke
    # dims pad every projection to one N-tile, so all real work sits on
    # shard 0 and the report must say exactly that
    assert sharded_meta["wq_shard_work"][0] > 0
    assert all(wk == 0 for wk in sharded_meta["wq_shard_work"][1:])
    assert sharded_meta["wq_max_layer_imbalance"] == pytest.approx(
        float(shards))
