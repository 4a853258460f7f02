"""How ``correct`` is decided, at sizes a CPU test run holds.

* The plain references compute the same function as the program's float
  path (they import nothing of it; the test compares the two).
* A whole run with the chip check skipped comes out correct, and comes out
  not correct when the timed path is broken underneath: a served token or
  an image's answer altered where the program produces it.
* The control (the reference in the precision below the stated one) fails
  the configured limit.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_chip_small as small
from benchmarks.chip import common, run
from benchmarks.chip.drivers import cnn_stream, lm_serving

BENCH = common.load_json(small.ROOT / "BENCHMARK.json")


def _cell(name, shrink):
    cell, cfg, mix = run.load_cell(BENCH, name)
    cfg, mix = shrink(cfg, mix)
    return cfg, mix, common.load_module(common.HERE / "configs" /
                                        cfg["reference"])


def test_lm_reference_matches_program_float_path():
    from repro.models.lm import LanguageModel
    from benchmarks.chip.quant import fake_quant

    cfg, _, ref = _cell("smollm-360m.chat", small.lm)
    params = lm_serving.float_weights(cfg, 3)
    w = ref.serving_weights(params)
    q = jax.tree_util.tree_map_with_path(
        lambda p, x: fake_quant(x) if str(p[-1].key) in
        ref.PROJECTIONS and x.ndim == 3 and p[0].key == "layers" else x,
        params)
    tokens = np.random.default_rng(0).integers(0, 512, 24).astype(np.int32)
    model = lm_serving.model_config(dict(cfg["model"], dtype="float32",
                                         impl="float"))
    with jax.default_matmul_precision("highest"):
        want = LanguageModel(model).logits(q, {"tokens": tokens[None]})[0]
    got = ref.logits(w, jnp.asarray(tokens), cfg["model"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_cnn_reference_matches_program_float_path():
    from repro.models import cnn
    from benchmarks.chip.quant import fake_quant

    cfg, mix, ref = _cell("vgg16-224.stream", small.cnn)
    params = cnn_stream.float_weights(cfg, 4)
    w = ref.serving_weights(params)
    q = {k: {"w": fake_quant(p["w"]), "b": p["b"]} for k, p in params.items()}
    x = np.asarray(cnn_stream.images_for(cfg, mix, 4))[:2]
    ccfg = cnn_stream.cnn_config(cfg["model"])
    with jax.default_matmul_precision("highest"):
        want = cnn.apply(q, jnp.asarray(x), ccfg, impl="float")
    got = ref.logits(w, jnp.asarray(x), cfg["model"])
    rel = cnn_stream.rel_errs(np.asarray(got), np.asarray(want)).max()
    assert rel < 1e-5


def _run_line(capsys, workload, shrink):
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 7),
                   "--seconds", "1", "--trace", "0"],
                  require_tpu=False, overrides=shrink, cache=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "token"])
def test_lm_run_correct_and_broken(capsys, monkeypatch, broken):
    if broken:
        from repro.inference.engine import ServingEngine
        select = ServingEngine._select

        def altered(self, logits, key):
            return (select(self, logits, key) + 1) % logits.shape[-1]
        monkeypatch.setattr(ServingEngine, "_select", altered)
    line = _run_line(capsys, "smollm-360m.chat", small.lm)
    assert line["correct"] is (not broken)
    assert list(line)[-1] == "checks"
    gap = line["checks"]["max_logit_gap"]
    assert (gap["value"] > gap["limit"]) is broken


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "answer"])
def test_cnn_run_correct_and_broken(capsys, monkeypatch, broken):
    if broken:
        from repro.inference.cnn_engine import CNNServingEngine
        logits = CNNServingEngine.logits
        monkeypatch.setattr(CNNServingEngine, "logits",
                            lambda self, x: logits(self, x)[..., ::-1])
    line = _run_line(capsys, "vgg16-224.stream", small.cnn)
    assert line["correct"] is (not broken)
    assert line["attempted"] > 0
    err = line["checks"]["max_rel_err"]
    assert (err["value"] > err["limit"]) is broken


def test_lm_control_fails_the_limit():
    """The control, the reference with its activations rounded to the
    configuration's control type (fp8 below the stated bf16), ranks other
    tokens first by more than the limit; rounded to bf16 it stays inside.
    The published widths and vocabulary over four layers, 384 positions."""
    cfg, _, ref = _cell("smollm-360m.chat", lambda c, m: (c, m))
    cfg["model"]["num_layers"] = 4
    vocab = cfg["model"]["vocab_size"]
    w = ref.serving_weights(lm_serving.float_weights(cfg, 1))
    ids = jnp.asarray(np.random.default_rng(1).integers(0, vocab, 384),
                      jnp.int32)
    best = np.asarray(ref.logits(w, ids, cfg["model"]), np.float64)

    def gap(act):
        tok = np.asarray(ref.logits(w, ids, cfg["model"], act)).argmax(-1)
        return float((best.max(-1) - best[np.arange(len(tok)), tok]).max())

    limit = cfg["check"]["max_logit_gap"]
    assert gap(cfg["check"]["control_activations"]) > limit
    assert gap("bfloat16") < limit


def test_cnn_control_fails_the_limit():
    """The control, the reference contracted in three bf16 passes (the
    precision below the stated fp32 passes), misses the limit."""
    cfg, mix, ref = _cell("vgg16-224.stream", small.cnn)
    x = np.asarray(cnn_stream.images_for(cfg, mix, 6))
    err = cnn_stream.check_err(cfg, 6, x, None, ref, passes="high3")
    assert err > cfg["check"]["max_rel_err"]
