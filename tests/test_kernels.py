"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Every Pallas kernel runs in interpret mode (CPU container; TPU is the
target) and must match its ref.py to f32-matmul tolerance.  Cross-impl
parity (float/int/planes/pallas agreement) comes from the shared
``parity`` harness — the sweep below and the per-bit-width cases both run
through it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import parity
import pytest

from repro.core import knead, quantize
from repro.core.schedule import replay_schedule
from repro.kernels.kneaded_gemm.ops import kneaded_gemm
from repro.kernels.kneaded_gemm.ref import kneaded_gemm_ref, pack_int4, unpack_int4
from repro.kernels.sac_matmul.ops import _pad_activations, sac_matmul_pallas
from repro.kernels.sac_matmul.ref import sac_matmul_ref


def _wa(seed, m, k, n, dtype=jnp.float32):
    kk = jax.random.split(jax.random.PRNGKey(seed), 2)
    w = jax.random.normal(kk[0], (k, n)) * 0.04
    a = jax.random.normal(kk[1], (m, k)).astype(dtype)
    return w, a


SHAPES = [
    (1, 256, 128),      # gemv (decode batch 1)
    (8, 256, 256),
    (16, 512, 128),
    (128, 512, 256),    # multi-tile M
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("bits", [4, 8, 9, 16])   # incl. odd width (paper §III.3)
def test_sac_kernel_shapes_bits(m, k, n, bits):
    parity.run_case(bits * 100 + m, m, k, n, bits=bits)


# the canonical cross-impl sweep (hypothesis-gated), kernel-tile shape pool
test_sac_impl_parity_sweep = parity.make_sweep_test()


# ------------------------------------------------- decode-GEMV M edge cases

@pytest.mark.parametrize("m", [1, 2, 7, 8, 9, 12])
def test_sac_kernel_tiny_m_bit_exact(m):
    """The M<8 clamp / small-M fast path must stay bit-exact vs the planes
    oracle — decode serves M=batch rows, often 1."""
    parity.run_case(m, m, 512, 128)


def test_pad_activations_m_policy():
    """bm_eff = min(bm, M rounded to the 8-row sublane floor): tiny M runs
    one small block, mid M an aligned single block, large M the full
    streamed grid; the padded row count is always a bm_eff multiple."""
    w, _ = _wa(0, 1, 512, 128)
    kw = knead(w, bits=8, ks=256, n_block=128)
    cases = [  # (m, bm) -> expected bm_eff
        (1, 256, 8), (7, 256, 8), (8, 256, 8),      # M<8 clamps to the floor
        (9, 256, 16), (12, 256, 16),                # round up, single block
        (40, 256, 40), (300, 256, 256),             # large M: streamed grid
        (5, 8, 8),                                  # caller cap respected
    ]
    for m, bm, want in cases:
        a = jnp.ones((m, 512))
        padded, m_out, bm_eff = _pad_activations(a, kw, bm)
        assert bm_eff == want, (m, bm, bm_eff, want)
        assert m_out == m
        assert padded.shape[0] % bm_eff == 0 and bm_eff % 8 == 0


def test_lm_matmul_streams_m_in_1024_row_blocks():
    """A prefill-sized LM launch takes M blocks of up to MATMUL_BM = 1024
    rows (oracle and kernel alike, so they stay operand-for-operand
    comparable); the conv entry keeps its 256-row blocks."""
    import inspect

    from repro.kernels.sac_matmul.ops import MATMUL_BM, m_block, sac_conv2d

    assert MATMUL_BM == 1024
    assert [m_block(m) for m in (1, 64, 520, 1024, 2048)] == \
        [8, 64, 520, 1024, 1024]
    assert inspect.signature(sac_conv2d).parameters["bm"].default == 256
    parity.run_case(520, 520, 512, 128)


def test_pad_activations_logical_k():
    """Logical-K activations zero-pad to the stored dim for any M, including
    the M<8 clamp; mismatched K still raises."""
    from repro.core.kneading import knead_padded

    w = jax.random.normal(jax.random.PRNGKey(3), (300, 100)) * 0.05
    kw = knead_padded(w, bits=8, ks=256)
    for m in (1, 7, 8):
        a = jnp.ones((m, 300))
        padded, m_out, bm_eff = _pad_activations(a, kw, 256)
        assert padded.shape[1] == kw.k and m_out == m and bm_eff == 8
    with pytest.raises(ValueError, match="neither"):
        _pad_activations(jnp.ones((1, 299)), kw, 256)


@pytest.mark.parametrize("adtype", [jnp.float32, jnp.bfloat16])
def test_sac_kernel_activation_dtypes(adtype):
    w, a = _wa(7, 8, 256, 128, dtype=adtype)
    kw = knead(w, bits=8, ks=256, n_block=128)
    ref = sac_matmul_ref(a.astype(jnp.float32), kw)
    out = sac_matmul_pallas(a, kw, bm=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_sac_kernel_occupancy_skipping_exact():
    """Zeroed high planes must not change the result (skipped, not wrong).

    The second K-block is ~100x smaller than the first; with per-channel
    scales set by the large block, its codes have empty high planes -> its
    (plane, K-tile) occupancy entries go to zero and the kernel skips them.
    """
    w, a = _wa(9, 8, 512, 128)
    w = w.at[256:].multiply(0.01)
    kw = knead(w, bits=16, ks=256, n_block=128)
    occ = np.asarray(kw.occupancy_map())
    assert occ.sum() < occ.size       # some tiles actually skip
    # the schedule dispatches exactly the occupied tiles, nothing more
    assert kw.schedule.total_work == int(occ.sum())
    assert kw.schedule.total_work < kw.schedule.dense_work(kw.bits)
    out = sac_matmul_pallas(a, kw, bm=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sac_matmul_ref(a, kw)),
                               rtol=1e-5, atol=1e-4)


def test_sac_kernel_runs_only_the_masked_planes():
    """One grid step runs its K-tile's planes under the bits of the slot's
    plane mask: clearing the bits of non-empty planes drops exactly those
    passes, bit for bit as the pass-by-pass replay of the same masks."""
    w, a = _wa(11, 8, 768, 256)
    kw = knead(w, bits=8, ks=256, n_block=128)
    sched = kw.schedule
    pbits = np.asarray(sched.plane_bits).copy()
    assert (pbits == 0x7F).all()     # dense: every plane of every K-tile
    pbits[0, 1] &= ~(1 << 6)         # N-tile 0, K-tile 1: drop the top plane
    pbits[1, 0] = 0b0000101          # N-tile 1, K-tile 0: planes 0 and 2 only
    cut = dataclasses.replace(kw, schedule=dataclasses.replace(
        sched, plane_bits=jnp.asarray(pbits),
        total_work=int(np.bitwise_count(pbits).sum())))
    out = np.asarray(sac_matmul_pallas(a, cut, bm=8))
    np.testing.assert_array_equal(out, np.asarray(replay_schedule(a, cut)))
    full = np.asarray(sac_matmul_pallas(a, kw, bm=8))
    for j in range(2):               # both N-tiles lost real passes
        cols = slice(j * 128, (j + 1) * 128)
        assert not np.array_equal(out[:, cols], full[:, cols])


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_kneaded_gemm_int8(m, k, n):
    w, a = _wa(m + k, m, k, n)
    qt = quantize(w, bits=8)
    scale = qt.scale.reshape(1, -1)
    ref = kneaded_gemm_ref(a, qt.q, scale)
    out = kneaded_gemm(a, qt.q, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_kneaded_gemm_int4_packed(m, k, n):
    w, a = _wa(m + k + 1, m, k, n)
    qt = quantize(w, bits=4)
    packed = pack_int4(qt.q)
    assert packed.shape == (k // 2, n)
    scale = qt.scale.reshape(1, -1)
    ref = kneaded_gemm_ref(a, packed, scale, packed4=True)
    out = kneaded_gemm(a, packed, scale, packed4=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_int4_pack_roundtrip():
    q = jnp.arange(-8, 8, dtype=jnp.int8).reshape(16, 1)
    q = jnp.tile(q, (2, 3))
    assert bool(jnp.array_equal(unpack_int4(pack_int4(q)), q))


def test_kernel_bytes_reduction():
    """The kneaded format's HBM footprint: bits/16 of bf16 + metadata."""
    w, _ = _wa(3, 1, 1024, 256)
    kw8 = knead(w, bits=8, ks=256)
    kw16 = knead(w, bits=16, ks=256)
    dense = kw8.dense_bf16_bytes()
    assert kw8.packed_bytes() < 0.75 * dense
    assert kw16.packed_bytes() < 1.5 * dense
    assert kw8.packed_bytes() < kw16.packed_bytes()


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_mode_by_backend(monkeypatch, backend, want):
    """Compiled on TPU, interpreted on CPU, refused anywhere else — a
    serving run never interprets the kernel on an accelerator."""
    from repro.kernels import backend as kb
    monkeypatch.setattr(kb.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            kb.interpret_mode()
    else:
        assert kb.interpret_mode() is want
