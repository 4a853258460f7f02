"""Operation and byte counts against hand-worked values, and the peak
table (an unknown device is an error)."""
import pytest

import bench_chip_small  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import common, counts

V5E = common.peaks_for("TPU v5 lite")


def _cfg(name):
    return common.load_json(common.HERE / "configs" / f"{name}.json")


def test_smollm_q_projection_by_hand():
    # one decode row through wq: [1, 960] x [960, 960], bf16 activations
    assert counts.matmul_flops(1, 960, 960) == 2 * 960 * 960 == 1_843_200
    # activations 960*2, weights 960*960 at one byte, 960 f32 scales,
    # output 960*2
    assert counts.matmul_bytes(1, 960, 960, 2) == 1920 + 921_600 + 3840 + 1920
    least, f, b = counts.least_time_s([(1, 960, 960)], 2, V5E)
    assert f == pytest.approx(1_843_200 / 197e12)
    assert b == pytest.approx(929_280 / 819e9)
    assert least == b                                  # bytes bound


def test_smollm_layer_projections():
    m = _cfg("smollm-360m")["model"]
    assert counts.lm_projections(m) == [(960, 960), (960, 320), (960, 320),
                                        (960, 960), (960, 2560), (960, 2560),
                                        (2560, 960)]
    per_layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    assert counts.lm_matmul_params(m) == 32 * per_layer + 960 * 49152
    assert len(counts.lm_kneaded_calls(m, 5)) == 32 * 7
    # one row at context 100: matmuls plus 4 * heads * head_dim * ctx / layer
    assert counts.lm_decode_flops(m, [100]) == pytest.approx(
        2 * counts.lm_matmul_params(m) + 4 * 15 * 64 * 100 * 32)
    # decode weight bytes at one byte per weight: about 315 MB a step
    assert sum(k * n for _, k, n in counts.lm_kneaded_calls(m, 1)) == \
        32 * per_layer == 314_572_800


def test_vgg_conv_layer_by_hand():
    m = _cfg("vgg16-224")["model"]
    layers = counts.cnn_layers(m)
    conv2 = layers[1]                      # 64 -> 64 at 224 x 224
    assert conv2 == {"kind": "conv", "rows": 224 * 224, "k": 576, "n": 64,
                     "ksize": 3, "in_c": 64}
    assert counts.matmul_flops(50176, 576, 64) == 3_699_376_128
    assert counts.matmul_bytes(50176, 576, 64, 4) == (
        50176 * 576 * 4 + 576 * 64 + 4 * 64 + 50176 * 64 * 4)
    fc1 = layers[13]
    assert fc1 == {"kind": "fc", "rows": 1, "k": 7 * 7 * 512, "n": 4096}
    assert [l["kind"] for l in layers].count("conv") == 13
    # VGG-16 at 224 px: 15.47 G multiply-adds (15.35 G conv + 0.12 G fc)
    assert counts.cnn_macs(m) == pytest.approx(15.47e9, rel=2e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        common.peaks_for("TPU v99")
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["int8_ops_per_s"] == 393e12


def test_percentile():
    assert common.percentile([1, 2, 3, 4, 5], 50) == 3
    assert common.percentile([0, 10], 90) == pytest.approx(9)
    assert common.percentile([], 90) != common.percentile([], 90)  # nan
