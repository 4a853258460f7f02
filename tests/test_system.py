"""End-to-end behaviour tests for the full system (the paper's technique as
a serving feature + training loop integration)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.inference.engine import (ServingConfig, ServingEngine,
                                    knead_params, serving_bytes)
from repro.models.lm import LanguageModel


@pytest.fixture(scope="module")
def small_lm():
    """An LM large enough (>=128-dim projections) for kneading to apply."""
    cfg = dataclasses.replace(
        get_config("llama3-8b", smoke=True),
        d_model=256, num_heads=4, num_kv_heads=2, d_ff=512, num_layers=2)
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_knead_params_reduces_serving_bytes(small_lm):
    cfg, model, params = small_lm
    b_f = serving_bytes(params)
    b_8 = serving_bytes(knead_params(params, bits=8))
    b_4 = serving_bytes(knead_params(params, bits=4))
    assert b_8 < 0.62 * b_f          # ~0.5x + embeddings/norms stay bf16
    assert b_4 < b_8


def test_kneaded_logits_close(small_lm):
    cfg, model, params = small_lm
    batch = {"tokens": jnp.arange(64, dtype=jnp.int32).reshape(2, 32)
             % cfg.vocab_size}
    lf = model.logits(params, batch).astype(jnp.float32)
    l8 = model.logits(knead_params(params, bits=8), batch).astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(lf - l8)) / (jnp.max(jnp.abs(lf)) + 1e-9))
    assert rel < 0.12                 # int8 kneading: small logit drift


def test_generation_across_precisions(small_lm):
    cfg, model, params = small_lm
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                 cfg.vocab_size)
    outs = {}
    for bits in (0, 8):
        eng = ServingEngine(cfg, params,
                            ServingConfig(max_len=48, quant_bits=bits))
        outs[bits] = eng.generate({"tokens": prompts}, 12)
    agree = float(jnp.mean((outs[8] == outs[0]).astype(jnp.float32)))
    assert agree > 0.6                # int8 mostly matches bf16 greedy


def test_prefill_decode_generation_consistency(small_lm):
    """Generating token-by-token must equal argmax over full forwards."""
    cfg, model, params = small_lm
    eng = ServingEngine(cfg, params, ServingConfig(max_len=48))
    prompts = jax.random.randint(jax.random.PRNGKey(5), (1, 8), 0,
                                 cfg.vocab_size)
    gen = eng.generate({"tokens": prompts}, 6)
    # reference: greedy with full forward each step
    toks = prompts
    ref = []
    for _ in range(6):
        logits = model.logits(params, {"tokens": toks})
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        ref.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    ref = jnp.stack(ref, 1)
    assert float(jnp.mean((gen == ref).astype(jnp.float32))) > 0.8


def test_example_loss_descends():
    """The synthetic stream is learnable: 60 steps must cut the loss."""
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig
    import shutil
    shutil.rmtree("/tmp/repro_test_descend", ignore_errors=True)
    cfg = get_config("smollm-360m", smoke=True)
    tr = Trainer(cfg, TrainerConfig(num_steps=60, ckpt_every=1000,
                                    ckpt_dir="/tmp/repro_test_descend",
                                    log_every=59),
                 ts=TrainStepConfig(optimizer=AdamWConfig(
                     lr=2e-3, warmup_steps=10, total_steps=60)),
                 global_batch=8, seq_len=64)
    log = tr.run()
    steps = sorted(log)
    assert log[steps[-1]]["loss"] < log[steps[0]]["loss"] - 0.3


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to one fixed directory in the checkout."""
    from pathlib import Path

    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = compile_cache.CHECKOUT_CACHE_DIR
            assert want.parent == Path(__file__).resolve().parents[1]
            assert compile_cache.enable_compile_cache() == str(want)
            assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
