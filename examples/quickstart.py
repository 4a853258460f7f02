"""Quickstart: the paper's technique end to end in ~60 lines.

1. Take a weight matrix, measure its zero-bit slack (Table 1).
2. Knead it (Fig 3) and show the cycle-count win of SAC over MAC (Fig 8).
3. Run the SAC matmul three ways — pure-jnp plane decomposition, integer
   epilogue, and the Pallas TPU kernel (interpret mode on CPU) — and check
   they agree bit-for-bit with the dense reference.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import (cost_model, knead, kneading_ratio, quantize,
                        sac_matmul, weight_bit_stats)
from repro.kernels.sac_matmul.ops import sac_matmul_pallas
from repro.kernels.sac_matmul.ref import sac_matmul_ref


def main():
    key = jax.random.PRNGKey(0)
    # a "trained-looking" heavy-tailed weight matrix (see EXPERIMENTS.md)
    w = jax.random.t(key, 3.0, (1024, 512)) * 0.02
    a = jax.random.normal(jax.random.PRNGKey(1), (8, 1024))

    # 1. bit-level slack (paper Table 1)
    s = weight_bit_stats(w, bits=16)
    print(f"zero-value weights: {100*s.zero_value_frac:.3f}%   "
          f"zero BITs in weights: {100*s.zero_bit_frac:.2f}%  "
          f"(paper: ~0.1% / ~68.9%)")

    # 2. kneading: cycles per 16-weight group vs the MAC baseline (Fig 3/11)
    qt = quantize(w, bits=16, axis=None)
    ratio = float(kneading_ratio(qt.q, 16, ks=16))
    print(f"kneaded cycle ratio at KS=16: {100*ratio:.1f}% of DaDN "
          f"(speedup {1/ratio:.2f}x)")

    # cycle model including the PRA baseline (Fig 8)
    acts = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (1024, 32)))
    cb = cost_model.model_layer(qt.q, quantize(acts, bits=16, axis=None).q,
                                bits=16, ks=16)
    print("modeled speedups vs DaDN:", {k: round(v, 2)
                                        for k, v in cb.speedup().items()})

    # 3. SAC matmul == dense matmul, three implementations
    kw = knead(w, bits=8, ks=256)
    dense = a @ (quantize(w, bits=8).q * quantize(w, bits=8).scale)
    for impl in ("planes", "int"):
        out = sac_matmul(a, kw, impl=impl)
        err = float(jnp.max(jnp.abs(out - dense)))
        print(f"sac_matmul[{impl:6s}] max err vs dense: {err:.2e}")
    out = sac_matmul_pallas(a, kw, bm=8)           # Pallas kernel
    err = float(jnp.max(jnp.abs(out - sac_matmul_ref(a, kw))))
    print(f"sac_matmul[pallas] max err vs oracle: {err:.2e}")
    print(f"kneaded HBM bytes vs bf16: "
          f"{kw.packed_bytes()/kw.dense_bf16_bytes():.3f}x")


if __name__ == "__main__":
    main()
