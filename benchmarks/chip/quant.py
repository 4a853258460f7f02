"""The benchmark's own copy of the 8-bit weight rule the serving path
states: symmetric, one scale per output channel (per layer for stacked
weights), codes in [-127, 127], round half to even, an all-zero channel
keeps scale 1.  The reference multiplies by ``codes * scale``."""
from __future__ import annotations

import jax.numpy as jnp


def fake_quant(w, bits: int = 8):
    """[..., K, N] -> dequantized [..., K, N] f32, scales over K."""
    w = w.astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    absmax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale
