"""Shared cross-impl parity harness (not collected — no ``test_`` prefix).

One place owns the "all four SAC execution paths agree" check that
``test_kernels``, ``test_cnn_kneaded``, and ``test_lm_kneaded`` previously
each hand-rolled: build a (sparse) weight, knead it, run every impl of
``repro.core.sac.sac_matmul``, and assert the agreement matrix

  * ``pallas == planes``  bit-exact (the kernel replays the compacted
    schedule's accumulation order — any unpack/sign/epilogue drift fails)
  * ``float == int``      bit-exact (identical math: one f32 matmul against
    the dequantized codes)
  * ``int ~= planes``     f32-matmul tolerance (same values, different
    accumulation order)
  * ``int ~= a @ dequantize(quantize(w))``  the quantized-model reference

``make_sweep_test`` stamps out the hypothesis-gated sweep over
shapes x sparsities x bits (gated like test_schedule.py: skips with a clear
reason when hypothesis is absent); each consumer binds one with its own
shape pool (kernel tiles, padded im2col dims, LM projections).
"""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import dequantize, quantize
from repro.core.kneading import knead, knead_padded
from repro.core.sac import SAC_IMPLS, sac_matmul

settings.register_profile("parity", deadline=None, max_examples=12)
settings.load_profile("parity")

# default sweep pools: M spans the GEMV/decode regime (1, 7) through the
# streamed-grid regime; K one and multiple kernel tiles; N one and two tiles
SWEEP_SHAPES = ((1, 256, 128), (7, 256, 128), (8, 512, 128), (24, 512, 256))
SWEEP_BITS = (4, 8)
SWEEP_SPARSITIES = (0.0, 0.7, 0.95)


def sparse_weight(seed: int, k: int, n: int, sparsity: float = 0.0,
                  scale: float = 0.05) -> jax.Array:
    """A random [K, N] weight with element sparsity (0.0 = dense)."""
    kk = jax.random.split(jax.random.PRNGKey(seed), 2)
    w = jax.random.normal(kk[0], (k, n)) * scale
    if sparsity > 0:
        keep = jax.random.uniform(kk[1], (k, n)) >= sparsity
        w = w * keep
    return w


def knead_case(seed: int, m: int, k: int, n: int, *, bits: int = 8,
               ks: int = 256, n_block: int = 128, sparsity: float = 0.0):
    """(activations [M, K], float weight [K, N], kneaded weight).

    Uses :func:`knead` for tile-aligned dims and :func:`knead_padded`
    otherwise, so arbitrary (im2col / LM head) dims flow through the same
    case builder.
    """
    w = sparse_weight(seed, k, n, sparsity)
    a = jax.random.normal(jax.random.PRNGKey(seed + 9973), (m, k))
    aligned = (k % np.lcm(32, ks) == 0) and (n % n_block == 0)
    kneader = knead if aligned else knead_padded
    return a, w, kneader(w, bits=bits, ks=ks, n_block=n_block)


def check_parity(a: jax.Array, w: jax.Array, kw, *, rtol: float = 1e-5,
                 atol: float = 1e-4) -> dict:
    """Assert the full impl agreement matrix; returns the per-impl outputs."""
    outs = {impl: np.asarray(sac_matmul(a, kw, impl=impl))
            for impl in SAC_IMPLS}
    np.testing.assert_array_equal(outs["pallas"], outs["planes"])
    np.testing.assert_array_equal(outs["float"], outs["int"])
    np.testing.assert_allclose(outs["int"], outs["planes"],
                               rtol=rtol, atol=atol)
    ref = np.asarray(
        a.astype(jnp.float32) @ dequantize(quantize(w, bits=kw.bits,
                                                    axis=-1)))
    np.testing.assert_allclose(outs["int"], ref, rtol=rtol, atol=atol)
    return outs


def run_case(seed: int, m: int, k: int, n: int, *, bits: int = 8,
             ks: int = 256, n_block: int = 128,
             sparsity: float = 0.0) -> dict:
    """Build a case and check it — the one-call form the sweeps use."""
    a, w, kw = knead_case(seed, m, k, n, bits=bits, ks=ks, n_block=n_block,
                          sparsity=sparsity)
    return check_parity(a, w, kw)


def make_sweep_test(shapes=SWEEP_SHAPES, bits=SWEEP_BITS,
                    sparsities=SWEEP_SPARSITIES, ks: int = 256,
                    n_block: int = 128):
    """A hypothesis-gated parity sweep over shapes x sparsities x bits.

    Bind the return value to a ``test_*`` name in a test module; when
    hypothesis is unavailable it collects as a skip with the install hint.
    """
    @given(seed=st.integers(0, 10), shape=st.sampled_from(list(shapes)),
           b=st.sampled_from(list(bits)),
           sparsity=st.sampled_from(list(sparsities)))
    def sweep(seed, shape, b, sparsity):
        m, k, n = shape
        run_case(seed, m, k, n, bits=b, ks=ks, n_block=n_block,
                 sparsity=sparsity)

    return sweep
