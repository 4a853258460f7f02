"""The Granite cell's harness pieces on the CPU: its reference against the
program's float path, a whole run at a small size that is correct and comes
out not correct when a served token is altered, the hybrid call counts
against the program's own kneaded leaves, and the Mamba match rule and its
two readers on hand-made trace events."""
import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_chip_small as small
from benchmarks.chip import common, counts, counts_hybrid, run
from benchmarks.chip import trace as tr
from benchmarks.chip.drivers import lm_serving

CELL = "granite-4.0-h-micro.chat"
BENCH = common.load_json(small.ROOT / "BENCHMARK.json")
# two periods of (one Mamba-2 layer, one attention layer) at small widths
HYBRID_MODEL = {"num_layers": 4, "attn_period": 2, "attn_offset": 1,
                "d_model": 128, "num_heads": 2, "num_kv_heads": 1,
                "head_dim": 64, "d_ff": 256, "vocab_size": 512,
                "ssm_state": 16, "ssm_head_dim": 32, "ssm_chunk": 8}
GRANITE = common.load_json(
    common.HERE / "configs" / "granite-4.0-h-micro.json")["model"]
MS = 1_000_000
SAC = ('%sac_matmul.1 = f32[64,8576] custom-call(bf16[64,2048] %a), '
       'custom_call_target="tpu_custom_call"')


def _small(cfg, mix):
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(HYBRID_MODEL)
    cfg["check"]["requests"] = 3
    return cfg, {**mix, **copy.deepcopy(small.LM_MIX)}


def _reader(name):
    return common.load_module(common.HERE / "metrics" / f"{name}.py")


def test_reference_matches_program_float_path():
    from repro.models.lm import LanguageModel
    from benchmarks.chip.quant import fake_quant

    _, cfg, mix = run.load_cell(BENCH, CELL)
    cfg, _ = _small(cfg, mix)
    ref = common.load_module(common.HERE / "configs" / cfg["reference"])
    params = lm_serving.float_weights(cfg, 3)
    q = jax.tree_util.tree_map_with_path(
        lambda p, x: fake_quant(x) if str(p[-1].key) in ref.PROJECTIONS
        else x, params)
    w = ref.serving_weights(params)
    tokens = np.random.default_rng(0).integers(0, 512, 29).astype(np.int32)
    model = lm_serving.model_config(dict(cfg["model"], dtype="float32",
                                         impl="float"))
    with jax.default_matmul_precision("highest"):
        want = LanguageModel(model).logits(q, {"tokens": tokens[None]})[0]
    got = ref.logits(w, jnp.asarray(tokens), cfg["model"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "token"])
def test_run_correct_and_broken(capsys, monkeypatch, broken):
    """A sound run at a small size is correct; one whose served tokens are
    altered where the program selects them is not."""
    if broken:
        from repro.inference.engine import ServingEngine
        select = ServingEngine._select

        def altered(self, logits, key):
            return (select(self, logits, key) + 1) % logits.shape[-1]
        monkeypatch.setattr(ServingEngine, "_select", altered)
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 7),
                   "--seconds", "1", "--trace", "0"],
                  require_tpu=False, overrides=_small, cache=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is (not broken)
    gap = line["checks"]["max_logit_gap"]
    assert (gap["value"] > gap["limit"]) is broken


def test_calls_are_the_kneaded_leaves():
    """Every kneaded projection is one call a row: the counts' weights per
    step are the K x N of the program's own kneadable leaves (in_proj,
    out_proj, q/k/v/o, gate/up/down) at published widths, 1.49 G weights
    (1.49 GB a step at one byte each)."""
    from repro.core.kneading import KNEADABLE_NAMES
    from repro.models.lm import LanguageModel

    shapes = jax.eval_shape(
        LanguageModel(lm_serving.model_config(GRANITE)).init,
        jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    kneaded = sum(x.size for p, x in leaves
                  if str(p[-1].key) in KNEADABLE_NAMES)
    calls = counts_hybrid.hybrid_kneaded_calls(GRANITE, 3)
    assert all(rows == 3 for rows, _, _ in calls)
    assert sum(k * n for _, k, n in calls) == kneaded == 1_492_385_792
    assert len(calls) == 18 * 5 + 2 * 7
    assert counts_hybrid.mamba_projections(GRANITE)[0] == (2048, 8512)


@pytest.mark.parametrize("name,expect", [
    # the state's in-place update, the conv window's, the gated norm
    ("%add_dynamic-update-slice_fusion.4 = f32[2,9,64,64,64,128]{5,4} "
     "fusion(f32[2,9,64,64,64,128]{5,4} %get-tuple-element.1, f32[64,64] "
     "%select_n.1), kind=kLoop, calls=%fused_computation.7", True),
    ("%fusion.12 = bf16[2,9,64,3,4352]{4,2} fusion(bf16[64,1,4352] %a), "
     "kind=kLoop", True),
    ("%fusion.3 = bf16[64,4096]{1,0} fusion(f32[64,4096] %y, "
     "f32[64,8576] %z), kind=kLoop", True),
    # attention, MLP, the residual, the head, a while, a weight's planes
    ("%fusion.9 = bf16[64,1,8,4,64]{4,3} fusion(bf16[64,2048] %q), "
     "kind=kLoop", False),
    ("%fusion.5 = bf16[64,8192]{1,0} fusion(f32[64,8192] %g), kind=kLoop",
     False),
    ("%fusion.6 = bf16[64,1,2048]{2,1} fusion(bf16[64,2048] %r), "
     "kind=kLoop", False),
    ("%convolution.2 = bf16[64,100352]{1,0} convolution(bf16[64,2048] %h, "
     "bf16[100352,2048] %e)", False),
    ("%while.7 = (s32[], f32[2,9,64,64,64,128]) while((s32[]) %t), "
     "condition=%c", False),
    ("%dynamic-slice.3 = u32[7,64,8576]{2,1,0} dynamic-slice(u32[2,9,7,64,"
     "8576] %planes, s32[] %g)", False),
    (SAC, False)])
def test_mamba_match_rule(name, expect):
    reader = _reader("mamba_share.decode")
    assert reader.is_mixer_op(tr.Op(name, 0, 1), GRANITE) is expect


def test_mamba_share_and_hybrid_roofline_readers():
    # a decode step 0-10 ms: a SAC call 2-5 ms, a mixer fusion 5-8 ms and
    # an MLP fusion 8-9 ms; the mixer shape outside the step does not count
    mixer = ("%fusion.3 = bf16[64,4096]{1,0} fusion(f32[64,4096] %y), "
             "kind=kLoop")
    ops = [tr.Op(SAC, 2 * MS, 5 * MS), tr.Op(mixer, 5 * MS, 8 * MS),
           tr.Op("%fusion.5 = bf16[64,8192] fusion(...)", 8 * MS, 9 * MS),
           tr.Op(mixer, 20 * MS, 21 * MS)]
    mods = [tr.Op("jit_decode_step(1)", 0, 10 * MS)]
    t = tr.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}}, [])
    s = tr.Summary(t, (0, 40 * MS))
    record = {"summary": s, "model": GRANITE,
              "steps": [{"contexts": [100, 200]}]}
    assert _reader("mamba_share.decode").read(record) == pytest.approx(30.0)
    peaks = common.peaks_for("TPU v5 lite")
    least, _, _ = counts.least_time_s(
        counts_hybrid.hybrid_kneaded_calls(GRANITE, 2), 2, peaks)
    assert _reader("sac_roofline.hybrid").read(record, peaks=peaks) == \
        pytest.approx(100 * least / 0.003)
    # a model without Mamba layers, or no trace: nothing to read
    assert _reader("mamba_share.decode").read(
        dict(record, model={"d_model": 960})) is None
    assert _reader("mamba_share.decode").read({"summary": None}) is None


def test_hybrid_decode_flops_and_mfu_reader():
    """One row at published widths: 2 x (1.49 G kneaded weights + the tied
    head's 2048 x 100352), 6 x 4096 x 128 state operations on each of the
    18 Mamba layers, and 4 x 32 heads x 64 per context position on each of
    the 2 attention layers; the reader divides the step's operations by its
    device time at the bf16 peak."""
    row = 2 * (1_492_385_792 + 2048 * 100352) + 6 * 4096 * 128 * 18
    assert counts_hybrid.hybrid_decode_flops(GRANITE, [0]) == row
    assert counts_hybrid.hybrid_decode_flops(GRANITE, [100, 200]) == \
        2 * row + 4 * 32 * 64 * 2 * 300
    mods = [tr.Op("jit_decode_step(1)", 0, 10 * MS)]
    t = tr.Trace({"/device:TPU:0": {"ops": [], "modules": mods}}, [])
    record = {"summary": tr.Summary(t, (0, 40 * MS)), "model": GRANITE,
              "steps": [{"contexts": [100, 200]}, {"contexts": []}]}
    peaks = common.peaks_for("TPU v5 lite")
    want = 100 * (2 * row + 4 * 32 * 64 * 2 * 300) / (
        0.010 * peaks["bf16_flops_per_s"])
    reader = _reader("mfu.hybrid")
    assert reader.read(record, peaks=peaks) == pytest.approx(want)
    # a dense model, or no trace: nothing to read
    assert reader.read(dict(record, model={"d_model": 960}),
                       peaks=peaks) is None
    assert reader.read({"summary": None}, peaks=peaks) is None
