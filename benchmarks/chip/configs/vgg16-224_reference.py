"""Plain float32 forward of VGG-16 (Simonyan & Zisserman 2014, configuration
D), in jax.numpy: 3x3 stride-1 SAME convolutions with ReLU, 2x2 max pools,
three fully connected layers (ReLU between), NHWC images.

No im2col and no kernel: each convolution is ``lax.conv_general_dilated``
on the filter that the layer's [C*3*3, out] weight matrix holds (patch
features in (channel, row, column) order, as the serving path lays them
out).  Every conv and fc weight is the 8-bit weight the server multiplies
by (``quant.fake_quant``, per output channel); biases stay float32.

``dot`` contracts two float32 operands: at ``highest`` for the reference,
or by the control's narrower rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip.quant import fake_quant


def serving_weights(params):
    return {name: {"w": fake_quant(p["w"]), "b": p["b"]}
            for name, p in params.items()}


def _conv(x, w, ksize, prec):
    c = x.shape[-1]
    filt = w.reshape(c, ksize, ksize, w.shape[-1]).transpose(1, 2, 0, 3)
    return jax.lax.conv_general_dilated(
        x, filt, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=prec, preferred_element_type=jnp.float32)


def _split3(op, a, b):
    """Three bf16 passes (hi*hi + hi*lo + lo*hi), as ``Precision.HIGH``
    contracts float32 on the MXU, written out so every backend agrees."""
    bf = jnp.bfloat16
    a_hi = a.astype(bf)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
    b_hi = b.astype(bf)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)
    p = jax.lax.Precision.DEFAULT
    return op(a_hi, b_hi, p) + op(a_hi, b_lo, p) + op(a_lo, b_hi, p)


def forward(w, images, model, passes="highest"):
    """images [B, H, W, C] -> logits [B, classes] float32."""
    hi = jax.lax.Precision.HIGHEST
    x = images.astype(jnp.float32)
    spec = model["spec"]
    for i, item in enumerate(spec):
        kind = item[0]
        if kind == "conv":
            p = w[f"conv{i}"]
            conv = functools.partial(_conv, ksize=item[2])
            if passes == "highest":
                y = conv(x, p["w"], prec=hi)
            else:
                y = _split3(lambda a, b, pr: conv(a, b, prec=pr), x, p["w"])
            x = jax.nn.relu(y + p["b"])
        elif kind == "pool":
            k = item[1]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, k, k, 1), "VALID")
        elif kind == "fc":
            x = x.reshape(x.shape[0], -1)
            p = w[f"fc{i}"]
            dot = lambda a, b, pr: jnp.dot(a, b, precision=pr,
                                           preferred_element_type=jnp.float32)
            y = dot(x, p["w"], hi) if passes == "highest" else _split3(
                dot, x, p["w"])
            x = y + p["b"]
            if i != len(spec) - 1:
                x = jax.nn.relu(x)
    return x


@functools.partial(jax.jit, static_argnames=("spec", "passes"))
def _logits(w, images, spec, passes):
    return forward(w, images, {"spec": spec}, passes)


def logits(w, images, model, passes="highest"):
    spec = tuple(tuple(item) for item in model["spec"])
    return _logits(w, images, spec, passes)
