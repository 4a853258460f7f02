"""Ahead-of-time compiles of the serving kernels for a TPU v5e, no chip.

Interpret mode runs the kernel bodies on the CPU but never asks Mosaic, the
TPU kernel compiler, whether it accepts them: unsupported casts, tiling,
VMEM budgets and scalar-prefetch index maps are only checked by a real
compile.  Each case here lowers one kernel at a real serving width with
``interpret=False`` against a described ``v5e:2x2`` topology and asserts
the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers that each import this file must collect the same tests.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.schedule import KneadedSchedule
from repro.kernels.kneaded_gemm.kernel import kneaded_gemm_pallas_call
from repro.kernels.sac_matmul.kernel import WORD, sac_matmul_pallas_call
from repro.models.lm import LanguageModel

BITS = 8
KS = 256          # ServingConfig.knead_ks / CNNServingConfig.ks
N_BLOCK = 128     # ServingConfig.knead_n_block / CNNServingConfig.n_block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_sac(one_chip, m, k, n, bm, a_dtype):
    """Compile the SAC kernel for [m, k] x kneaded [k, n] with a dense
    schedule (every (plane, K-tile) item present — the largest walk)."""
    nk, n_tiles = k // KS, n // N_BLOCK
    num_work = (BITS - 1) * nk

    def run(a, planes, signs, scale, counts, pids, kids, mask):
        sched = KneadedSchedule(counts=counts, plane_ids=pids, ktile_ids=kids,
                                num_work=num_work,
                                total_work=num_work * n_tiles,
                                nk=nk, n_tiles=n_tiles)
        return sac_matmul_pallas_call(a, planes, signs, scale, sched,
                                      bits=BITS, bm=bm, bn=N_BLOCK, bk=KS,
                                      interpret=False, mask=mask)

    sched_shape = (n_tiles, num_work)
    args = (_sds((m, k), a_dtype, one_chip),
            _sds((BITS - 1, k // WORD, n), jnp.uint32, one_chip),
            _sds((k // WORD, n), jnp.uint32, one_chip),
            _sds((1, n), jnp.float32, one_chip),
            _sds((n_tiles,), jnp.int32, one_chip),
            _sds(sched_shape, jnp.int32, one_chip),
            _sds(sched_shape, jnp.int32, one_chip),
            _sds(sched_shape, jnp.int32, one_chip))
    return jax.jit(run).lower(*args).compile()


@pytest.mark.parametrize("k,n", [(1024, 1024), (1024, 2560), (2560, 1024)],
                         ids=["qo", "gate_up", "down"])
def test_sac_kernel_compiles_smollm_decode(one_chip, k, n):
    """smollm-360m decode GEMV: d_model 960 -> 1024 and d_ff 2560 kneaded,
    bf16 activations at the 8-row M block."""
    compiled = _compile_sac(one_chip, 8, k, n, bm=8, a_dtype=jnp.bfloat16)
    assert "tpu_custom_call" in compiled.as_text()


def test_sac_kernel_compiles_smollm_prefill(one_chip):
    """smollm-360m prefill: two streamed 256-row M blocks."""
    compiled = _compile_sac(one_chip, 512, 1024, 2560, bm=256,
                            a_dtype=jnp.bfloat16)
    assert "tpu_custom_call" in compiled.as_text()


def test_sac_kernel_compiles_vgg16_conv(one_chip):
    """A VGG-16 512-channel 3x3 conv as im2col: K = 512*9 = 4608 -> N 512,
    f32 activations, one batch-8 2x2 feature map of rows streamed."""
    compiled = _compile_sac(one_chip, 8 * 2 * 2 * 16, 4608, 512, bm=256,
                            a_dtype=jnp.float32)
    assert "tpu_custom_call" in compiled.as_text()


def test_kneaded_gemm_int8_compiles(one_chip):
    m, k, n = 256, 1024, 2560
    args = (_sds((m, k), jnp.bfloat16, one_chip),
            _sds((k, n), jnp.int8, one_chip),
            _sds((1, n), jnp.float32, one_chip))
    compiled = jax.jit(lambda a, q, s: kneaded_gemm_pallas_call(
        a, q, s, bm=256, bn=256, bk=512, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernels_keep_their_names_inside_jitted_wrappers(one_chip):
    """The profiler names a kernel's ops after the HLO instruction, which
    takes the ``pallas_call`` name even when the kernel runs through the
    jitted wrappers of ``ops.py`` inside a jitted step."""
    from repro.kernels.kneaded_gemm import ops as gemm_ops
    from repro.kernels.sac_matmul import ops as sac_ops

    m, k, n = 8, 1024, 1024
    nk, n_tiles = k // KS, n // N_BLOCK
    num_work = (BITS - 1) * nk

    def sac_step(a, planes, signs, scale, counts, pids, kids, mask):
        sched = KneadedSchedule(counts=counts, plane_ids=pids, ktile_ids=kids,
                                num_work=num_work,
                                total_work=num_work * n_tiles,
                                nk=nk, n_tiles=n_tiles)
        return jnp.tanh(sac_ops._run(a, planes, signs, scale, sched, mask,
                                     bits=BITS, ks=KS, n_block=N_BLOCK, bm=8,
                                     interpret=False))

    sched_shape = (n_tiles, num_work)
    sac = jax.jit(sac_step).lower(
        _sds((m, k), jnp.bfloat16, one_chip),
        _sds((BITS - 1, k // WORD, n), jnp.uint32, one_chip),
        _sds((k // WORD, n), jnp.uint32, one_chip),
        _sds((1, n), jnp.float32, one_chip),
        _sds((n_tiles,), jnp.int32, one_chip),
        *[_sds(sched_shape, jnp.int32, one_chip)] * 3).compile().as_text()
    gemm = jax.jit(lambda a, q, s: jnp.tanh(gemm_ops._run(
        a, q, s, packed4=False, bm=8, bn=256, bk=512, interpret=False))
    ).lower(_sds((m, k), jnp.bfloat16, one_chip),
            _sds((k, n), jnp.int8, one_chip),
            _sds((1, n), jnp.float32, one_chip)).compile().as_text()
    for text, name in ((sac, "sac_matmul"), (gemm, "kneaded_gemm")):
        calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert calls and all(c.startswith(f"%{name}") for c in calls), calls


def _top_level_ops(hlo_text):
    """(opcode, output type) of every instruction outside the fused
    computations of a compiled HLO module: the ops the device runs one by
    one, each reading and writing its operands and output in HBM."""
    fused = set(re.findall(r"\bfusion\(.*?calls=(%[\w.\-]+)", hlo_text))
    ops, comp = [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1] if line.startswith("ENTRY") else \
                line.split()[0]
            continue
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(",
                     line)
        if m and comp not in fused:
            kind = re.search(r"kind=(k\w+)", line)
            ops.append((m.group(2) + (f":{kind.group(1)}" if kind else ""),
                        m.group(1)))
    return ops


def test_lm_decode_step_writes_the_kv_cache_in_place(one_chip):
    """smollm-360m's dense decode step at its serving widths (2 layers,
    64 rows, a 1536-position bf16 cache, donated) writes only the new
    token's K/V: no whole-layer or whole-cache temporary, no op that
    rewrites the cache, and no Mosaic kernel of its own.  Weights are
    bf16: f32 ones add a 94 MB bf16 copy of the embedding table, which
    has nothing to do with the cache, to the temporaries."""
    cfg = dataclasses.replace(get_config("smollm-360m"), num_layers=2)
    model = LanguageModel(cfg)
    b, max_len = 64, 1536
    params = jax.tree.map(
        lambda x: _sds(x.shape, jnp.bfloat16, one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    sds = lambda x: _sds(x.shape, x.dtype, one_chip)
    cache = jax.tree.map(sds, model.cache_spec(b, max_len))
    compiled = jax.jit(model.decode_step, donate_argnums=(3,)).lower(
        params, _sds((b, 1), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip), cache).compile()
    layer_k_bytes = b * max_len * cfg.num_kv_heads * cfg.hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
    text = compiled.as_text()
    shapes = (f"[{b},{max_len},{cfg.num_kv_heads},{cfg.hd}]",
              f"[{cfg.num_layers},{b},{max_len},{cfg.num_kv_heads},{cfg.hd}]")
    rewrites = [(op, out) for op, out in _top_level_ops(text)
                if op in ("select", "copy", "scatter", "fusion:kLoop")
                and any(s in out for s in shapes)]
    assert not rewrites, rewrites
    assert "tpu_custom_call" not in text
