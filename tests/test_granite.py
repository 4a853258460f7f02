"""granite-4.0-h's hybrid body (``attn_period``): Mamba-2 layers with
per-slot recurrent state beside attention layers with KV, an MLP after
every mixer, served through ServingEngine and the continuous scheduler,
against the benchmark's plain float32 reference.

Small widths (d_model 64, 8 layers in periods of 4, d_state 16, vocab 512),
the model in float32 so that the comparison is tight, and Mamba-2's
published initialisation of A_log and dt, so that the state carries far:
with A = -exp(A_log) in [-16, -1] and dt in [1e-3, 1e-1] a token decays by
exp(-dt |A|) >= exp(-1.6), most by far less.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import common  # noqa: E402
from benchmarks.chip.drivers import lm_serving  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.inference.engine import ServingConfig, ServingEngine  # noqa: E402
from repro.models import ssm  # noqa: E402

CHUNK = 8
SMALL = {"num_layers": 8, "attn_period": 4, "attn_offset": 1, "d_model": 64,
         "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
         "vocab_size": 512, "ssm_state": 16, "ssm_head_dim": 16,
         "ssm_chunk": CHUNK, "attn_scale": 1 / 16, "dtype": "float32"}


@pytest.mark.parametrize("length", [1, 7, 8, 29])
def test_ssd_chunked_any_length_matches_the_step_recurrence(length):
    """A length that is no multiple of the chunk runs: the padded steps
    (u = 0, log a = 0) leave the state as it was, and every output and the
    final state equal the token-by-token recurrence."""
    rng = np.random.default_rng(length)
    b, h, p, n = 2, 3, 4, 5
    u = jnp.asarray(rng.normal(size=(b, length, h, p)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, length, h, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(b, length, h, n)), jnp.float32)
    log_a = jnp.asarray(-rng.uniform(0.01, 0.5, (b, length, h)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, h, p, n)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, h_last = ssm.ssd_chunked(u, bb, cc, log_a, CHUNK, h0=h0)
        state, ys = h0, []
        for t in range(length):
            y_t, state = ssm.ssd_step(u[:, t], bb[:, t], cc[:, t],
                                      log_a[:, t], state)
            ys.append(y_t)
    assert y.shape == (b, length, h, p)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_last, state, rtol=1e-5, atol=1e-5)


def test_attn_period_must_tile_the_layers():
    with pytest.raises(ValueError, match="attn_period"):
        ModelConfig(family="hybrid", num_layers=6, attn_period=4)
    with pytest.raises(ValueError, match="attn_offset"):
        ModelConfig(family="hybrid", num_layers=8, attn_period=4,
                    attn_offset=4)


def _granite(seed=5):
    """The cell's configuration at the small widths, the benchmark's weight
    draw, then Mamba-2's own init for A_log, dt_bias and D, PyTorch's for
    the depthwise conv (U(-1/2, 1/2) at fan-in 4), and in_proj at std
    0.02 x 8: at d_model 64 the cell's 0.02 leaves x, B and C so small
    that D x would drown the state's part of y."""
    cfg = common.load_json(common.HERE / "configs" / "granite-4.0-h-micro.json")
    cfg["model"].update(SMALL)
    params = lm_serving.float_weights(cfg, seed)
    mixer = params["groups"]["mamba"]["mixer"]
    rng = np.random.default_rng(seed)
    heads = mixer["a_log"].shape
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), heads))
    mixer["a_log"] = jnp.asarray(np.log(rng.uniform(1, 16, heads)),
                                 jnp.float32)
    mixer["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    mixer["d_skip"] = jnp.ones(heads, jnp.float32)
    mixer["in_proj"] = mixer["in_proj"] * 8
    for name in ("conv_w", "conv_b"):
        mixer[name] = jnp.asarray(
            rng.uniform(-0.5, 0.5, mixer[name].shape), jnp.float32)
    return cfg, params


def _record_logits(engine):
    """Every logits row the scheduler selects a token from, per request:
    the prefill's row (group order) and then one decode row a step (the
    request's slot).  Also the slots each request was given."""
    sched = engine._scheduler
    rows, slots = {}, {}
    select = engine._select

    def recording(logits, key):
        host = np.asarray(logits)
        live = [(i, r) for i, r in enumerate(sched.slots) if r is not None]
        fresh = sorted((r for _, r in live if not r.out), key=lambda r: r.id)
        if fresh:
            for i, r in enumerate(fresh):
                rows[r.id] = [host[i]]
                slots[r.id] = r.slot
        else:
            for slot, r in live:
                rows[r.id].append(host[slot])
        return select(logits, key)
    engine._select = recording
    return rows, slots


@pytest.mark.parametrize("impl", ["planes", "pallas"])
def test_serving_engine_matches_the_reference(impl):
    """Prefill then decode through ServingEngine and the continuous
    scheduler gives, at every served position, the logits of the plain
    reference's full forward over the prompt and the tokens served before
    it.  Prompts of 5, 11, 19 and 13 tokens (none a multiple of the SSD
    chunk of 8) through two slots, so the last two requests are admitted
    into slots that served and freed a request before them: a slot's
    state, conv window and KV are overwritten on admission.

    Tolerance 2e-4 of the largest logit: the program and the reference
    multiply the same 8-bit weights in float32 and differ only in the
    order of float32 sums (chunked SSD against the step recurrence,
    bit-plane sums against one dot); a wrong mixer, gate order or
    multiplier moves logits by 1e-2 or more of their scale."""
    cfg, params = _granite()
    ref = common.load_module(common.HERE / "configs" / cfg["reference"])
    engine = ServingEngine(
        lm_serving.model_config(cfg["model"]), params,
        ServingConfig(impl=impl, quant_bits=8, scheduler="continuous",
                      max_inflight=2, max_len=32, kv_block=16,
                      buckets=(1, 2), knead_min_dim=16))
    rows, slots = _record_logits(engine)
    rng = np.random.default_rng(11)
    asks = [(5, 6), (11, 3), (19, 5), (13, 4)]
    prompts = [rng.integers(0, 512, p).astype(np.int32) for p, _ in asks]
    handles = [engine.submit(jnp.asarray(t), n)
               for t, (_, n) in zip(prompts, asks)]
    served = [np.asarray(h.result()) for h in handles]
    assert sorted(slots.values()) == [0, 0, 1, 1]
    w = ref.serving_weights(jax.tree.map(lambda x: x, params))
    worst = 0.0
    for h, prompt, out in zip(handles, prompts, served):
        seq = np.concatenate([prompt, out[:-1]])
        want = np.asarray(ref.logits(w, jnp.asarray(seq), cfg["model"]))
        got = np.stack(rows[h])
        assert got.shape == (len(out), 512)
        want = want[len(prompt) - 1:]
        worst = max(worst, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
    assert worst < 2e-4, worst


def test_recurrent_state_matters_here():
    """The test above sees the Mamba state: zeroing the layers' states
    after an 18-token prefill moves the next decode logits by 50 times
    its tolerance."""
    cfg, params = _granite()
    from repro.models.lm import LanguageModel
    model = LanguageModel(lm_serving.model_config(cfg["model"]))
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 19)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, cache = model.prefill(params, {"tokens": tokens[:, :18]})
        pad = lambda x: jnp.pad(x, [(0, 0)] * 2 + [(0, 14)] + [(0, 0)] * 2)
        cache = dict(cache, k=pad(cache["k"]), v=pad(cache["v"]))
        pos = jnp.array([18], jnp.int32)
        good, _ = model.decode_step(params, tokens[:, 18:], pos, cache)
        cut = dict(cache, ssm=jnp.zeros_like(cache["ssm"]))
        bad, _ = model.decode_step(params, tokens[:, 18:], pos, cut)
    assert float(jnp.abs(good - bad).max() / jnp.abs(good).max()) > 1e-2
