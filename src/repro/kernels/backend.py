"""How the Pallas kernels run: compiled on TPU, interpreted on CPU, and
at which MXU precision their f32 dots contract.

The CPU path is the test path — interpret mode executes the kernel body
faithfully at small shapes.  Any other backend is an error rather than a
silent interpreter run, so a serving run can never pass on a device that
never executed the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def exact_dot_precision(activation_dtype) -> jax.lax.Precision | None:
    """MXU precision for an in-kernel f32 dot whose weight operand is exact
    in bf16 (bit planes in {-1, 0, +1}, int8 codes).

    Mosaic's default contracts f32 operands in one bf16 pass, which rounds
    f32 activations (1.4e-3 relative error measured on a TPU v5e).  bf16
    activations lose nothing in that pass, so they keep it; anything wider
    asks for the fp32 contract."""
    if jnp.dtype(activation_dtype) == jnp.bfloat16:
        return None
    return jax.lax.Precision.HIGHEST


def interpret_mode() -> bool:
    """``interpret=`` for a ``pallas_call`` on the default backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels compile for TPU and interpret "
                       f"on CPU only; default backend is {backend!r}")
