"""Split-and-Accumulate (SAC) — the paper's computing pattern, as JAX ops.

MAC computes ``sum_i A_i * W_i`` pair-wise.  SAC (paper Eq. 2) regroups by bit:

    sum_i A_i * W_i  =  sum_b 2^b * ( sum_i A_i * W_i^b )

keeping one *segment accumulator* per bit position and performing the
shift-and-add **once** at the end (the rear adder tree).  Three interchangeable
implementations, all numerically identical on quantized weights:

* ``impl="planes"`` — the paper-faithful decomposition: one MXU pass per
  non-empty bit plane, per-plane segment accumulators, single 2^b reduction.
  (Pure jnp; the Pallas kernel in ``repro.kernels.sac_matmul`` is the tiled
  TPU version driven by the compacted occupancy schedule — this is its
  semantic oracle and replays the schedule's accumulation order.)
* ``impl="int"``    — the production path: one integer-code matmul with the
  scale applied once in the epilogue (SAC's "defer all shifting/scaling to
  the rear" applied at tile granularity).  Same math, MXU-optimal.
* ``impl="pallas"`` — dispatch to the Pallas kernel (interpret=True on CPU).

All paths return ``A @ dequantize(Wq)`` exactly (float32 accumulation).
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import bitplanes
from repro.core.kneading import KneadedWeight, ShardedKneadedWeight, knead

__all__ = ["SAC_IMPLS", "sac_matmul", "sac_matmul_planes", "sac_matmul_int",
           "TetrisLinear"]


def sac_matmul_planes(a: jax.Array, kw: KneadedWeight) -> jax.Array:
    """Paper-faithful SAC: per-plane matmuls + single rear shift-and-add.

    Replays the Pallas kernel's *compacted-schedule order*: K tiles of extent
    ``ks`` ascend (the schedule's walk) with planes walked within each tile,
    each partial dot accumulating into its plane's segment S_b.
    The passes the schedule never dispatches are exactly the all-zero
    plane tiles, whose partial is exactly 0.0 — adding it is a bitwise no-op
    — so this dense replay realizes the same per-segment accumulation
    sequence as the compacted kernel, and the parity tests assert bit-exact
    *equality*, not closeness.  (``repro.core.schedule.replay_schedule`` is
    the pass-by-pass sparse replay; the property tests pin all three paths
    equal.)  Output = scale * sum_b 2^b S_b — the single rear adder tree,
    summed in plane order as the kernel's epilogue does.

    Each partial dot has the kernel's tile shape, [M, ks] x [ks, n_block]:
    XLA CPU blocks a wider dot differently, which moves f32 rounding by an
    ulp, so a dense-N replay is not bitwise comparable.
    """
    mag = bitplanes.unpack_bits(kw.planes, axis=1)                 # [B-1, K, N]
    sign = 1 - 2 * bitplanes.unpack_bits(kw.signs, axis=0).astype(jnp.int8)
    a32 = a.astype(jnp.float32)
    nk = kw.k // kw.ks
    bn = kw.n_block
    planes = [(mag[b].astype(jnp.int8) * sign).astype(jnp.float32)
              for b in range(kw.bits - 1)]
    cols = []
    for j in range(kw.n // bn):              # output tiles
        nsl = slice(j * bn, (j + 1) * bn)
        segments = [jnp.zeros((a32.shape[0], bn), jnp.float32)
                    for _ in range(kw.bits - 1)]
        for t in range(nk):                  # K tiles ascending (grid order)
            sl = slice(t * kw.ks, (t + 1) * kw.ks)
            for b in range(kw.bits - 1):     # planes within the K tile
                segments[b] = segments[b] + a32[:, sl] @ planes[b][sl, nsl]
        out = segments[0]                    # rear adder
        for b in range(1, kw.bits - 1):
            out = out + segments[b] * float(2 ** b)
        cols.append(out)
    return jnp.concatenate(cols, axis=1) * kw.scale                # scale once


def sac_matmul_int(a: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """Integer-code matmul with deferred (epilogue) scaling.

    ``q`` is the signed code matrix [K, N]; scale broadcast [1, N].  f32
    accumulation; codes cast to f32 are exact for |q| < 2^24 (bits <= 16).
    """
    out = jnp.dot(a.astype(jnp.float32), q.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return out * scale


SAC_IMPLS = ("float", "int", "planes", "pallas")


def sac_matmul(
    a: jax.Array,
    kw: KneadedWeight,
    impl: Literal["float", "planes", "int", "pallas"] = "int",
    *,
    skip_activations: bool = False,
) -> jax.Array:
    """SAC matmul of activations [..., K] against a kneaded weight [K, N].

    Accepts activations sized to either the stored (padded) or the logical
    reduction dim: logical inputs are zero-padded up to ``kw.k`` and the
    output is sliced back to ``kw.logical_n`` — exact, since padded rows/
    channels are all-zero codes.

    ``skip_activations=True`` arms the runtime activation-side skip
    (docs/DESIGN.md §12) on the Pallas paths, gated to the decode-GEMV
    regime: it engages only when the flattened activation has at most
    ``GEMV_ROWS_MAX`` (8) rows — a decode step — where per-K-tile presence
    bits from the activation row are intersected into the kernel's schedule
    walk.  Prefill-shaped calls (M > 8) silently fall back to the static
    weight-only skip: unioned presence over hundreds of rows is all ones,
    so masking would cost runtime for zero skipped work.  The switch never
    changes results on any impl: dropped items contribute exactly 0.0, so
    the non-pallas impls ("planes"/"int"/"float"), which ignore the flag,
    double as the skip-off oracles the parity tests compare against.

    impl="float" dequantizes the codes and runs one f32 matmul — the
    quantized-model reference the SAC paths must match (identical math to
    "int"; kept so the model-level dispatch matrix is closed under this op).

    N-sharded weights (``ShardedKneadedWeight``, including per-layer
    scan slices of a ``ShardedStackedKneadedWeight``) execute through the
    Pallas kernel only — one launch per device of the serving mesh
    installed via :func:`repro.runtime.sharding.serving_mesh`, or the
    serial single-device shard walk when no mesh is installed (the parity
    oracle; docs/DESIGN.md §8).
    """
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    if a2.shape[1] not in (kw.k, kw.logical_k):
        raise ValueError(
            f"activation K {a2.shape[1]} matches neither stored "
            f"{kw.k} nor logical {kw.logical_k}")
    from repro.core.activation_occupancy import GEMV_ROWS_MAX
    skip = bool(skip_activations) and a2.shape[0] <= GEMV_ROWS_MAX
    if isinstance(kw, ShardedKneadedWeight):
        if impl != "pallas":
            raise ValueError("sharded kneaded weights execute through the "
                             f"Pallas kernel only, got impl={impl!r}")
        if kw.planes.ndim == 5:
            raise ValueError(
                "a stacked sharded weight reached sac_matmul un-sliced — "
                "scan over its layer axis (or index one layer) first")
        from repro.kernels.sac_matmul.ops import sac_matmul_pallas_sharded
        from repro.runtime.sharding import current_serving_mesh
        mesh, axis = current_serving_mesh()
        out = sac_matmul_pallas_sharded(a2, kw, mesh, axis,
                                        skip_activations=skip)
    elif impl == "pallas":
        # the ops-level wrapper owns the logical-K zero-pad policy
        from repro.kernels.sac_matmul.ops import sac_matmul_pallas
        out = sac_matmul_pallas(a2, kw, skip_activations=skip)
    else:
        if a2.shape[1] != kw.k:
            a2 = jnp.pad(a2, ((0, 0), (0, kw.k - a2.shape[1])))
        if impl == "planes":
            # Replay the kernel's padded M: the pallas grid rounds M up to
            # its block (zero rows — exact), and XLA CPU picks *different*
            # dense-matmul micro-kernels for, e.g., M=7 vs M=8 at wide N,
            # which changes f32 reduction order at ~1e-6.  Padding here
            # keeps the oracle operand-for-operand comparable, so planes ==
            # pallas stays bitwise at every M.
            from repro.kernels.sac_matmul.ops import m_block
            m0 = a2.shape[0]
            pad = (-m0) % m_block(m0)
            if pad:
                a2 = jnp.pad(a2, ((0, pad), (0, 0)))
            out = sac_matmul_planes(a2, kw)[:m0]
        elif impl in ("int", "float"):
            from repro.core.kneading import unknead  # codes * scale, exact
            out = a2.astype(jnp.float32) @ unknead(kw)
        else:
            raise ValueError(f"unknown impl {impl!r}")
    out = out[:, :kw.logical_n]
    return out.reshape(lead + (kw.logical_n,)).astype(a.dtype)


class TetrisLinear:
    """A linear layer whose weights live in kneaded form (serving path).

    Functional: ``TetrisLinear.knead_params(w, bits, ks)`` converts a trained
    float [K, N] kernel; ``TetrisLinear.apply(params, x)`` runs SAC matmul.
    """

    @staticmethod
    def knead_params(w: jax.Array, bits: int = 8, ks: int = 256) -> KneadedWeight:
        return knead(w, bits=bits, ks=ks)

    @staticmethod
    def apply(params: KneadedWeight, x: jax.Array,
              impl: Literal["float", "planes", "int", "pallas"] = "int",
              ) -> jax.Array:
        return sac_matmul(x, params, impl=impl)
