"""Jitted public wrapper for the kneaded integer GEMM kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.kneaded_gemm.kernel import kneaded_gemm_pallas_call
from repro.kernels.kneaded_gemm.ref import pack_int4


@functools.partial(
    jax.jit, static_argnames=("packed4", "bm", "bn", "bk", "interpret"))
def _run(a, q, scale, *, packed4, bm, bn, bk, interpret):
    return kneaded_gemm_pallas_call(
        a, q, scale, packed4=packed4, bm=bm, bn=bn, bk=bk, interpret=interpret)


def kneaded_gemm(
    a: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    packed4: bool = False,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
) -> jax.Array:
    """Integer-kneaded GEMM with deferred scale; pads M to the tile size."""
    m, k = a.shape
    n = q.shape[-1]
    bm_eff = min(bm, max(8, m))
    bn_eff = min(bn, n)
    bk_eff = min(bk, k)
    pad = (-m) % bm_eff
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
    out = _run(a, q, scale.reshape(1, -1).astype(jnp.float32),
               packed4=packed4, bm=bm_eff, bn=bn_eff, bk=bk_eff,
               interpret=interpret_mode())
    return out[:m] if pad else out


def pack_weights_int4(q8: jax.Array) -> jax.Array:
    """Nibble-pack int8 codes in [-8, 7] (bits=4 quantization) along K."""
    return pack_int4(q8)
