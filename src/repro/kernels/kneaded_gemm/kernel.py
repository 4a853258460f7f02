"""Pallas TPU kernel: kneaded integer GEMM (int8 / nibble-packed int4).

The beyond-paper production variant of SAC for serving: instead of one MXU
pass per bit plane, the integer codes are kept *packed in HBM* (1 B or 0.5 B
per weight vs 2 B bf16 — a 2x/4x cut of the decode memory-roofline term),
unpacked in VMEM, and multiplied in a single MXU pass per tile.  The SAC
principle survives as the *deferred epilogue*: no intermediate pair-wise
dequantized products ever exist; the per-channel scale ("rear adder tree +
scale") is applied exactly once per output tile.

Grid (M/bm, N/bn, K/bk), K innermost, f32 VMEM scratch accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import exact_dot_precision


def _kernel(a_ref, q_ref, scale_ref, out_ref, acc_ref, *, nk: int, packed4: bool):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    q = q_ref[...]
    if packed4:
        low = jnp.right_shift(jnp.left_shift(q, 4), 4)   # sign-extend
        high = jnp.right_shift(q, 4)
        kw, bn = q.shape
        q = jnp.stack([low, high], axis=1).reshape(kw * 2, bn)
    w = q.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        a, w, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=exact_dot_precision(a_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...] * scale_ref[...]     # scale applied ONCE


def kneaded_gemm_pallas_call(
    a: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    packed4: bool = False,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """[M, K] @ int codes [K, N] (or [K/2, N] packed int4) -> [M, N] f32."""
    m, k = a.shape
    kq, n = q.shape
    assert kq * (2 if packed4 else 1) == k, (kq, k, packed4)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk
    bkq = bk // 2 if packed4 else bk
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, packed4=packed4),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkq, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="kneaded_gemm",
    )(a, q, scale)
