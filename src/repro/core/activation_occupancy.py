"""Runtime activation-side occupancy — the two-sided skip (docs/DESIGN.md §12).

Tetris kneads slack out of the *weight* side at knead time: the compacted
:class:`~repro.core.schedule.KneadedSchedule` is static — built once from the
weight occupancy map, walked unchanged every step.  The activation side is
*dynamically* sparse (Cnvlutin2 / Laconic in PAPERS.md): a ReLU trace or an
MoE residual can zero whole reduction ranges, and a K-tile whose activation
slice is all zero contributes exactly ``A_t @ P_bt == 0`` through every bit
plane it holds.

This module is the runtime half of the intersection.  Per SAC call it

1. computes per-K-tile presence bits from the activation block
   (:func:`ktile_presence` — a reshape + ``any``, one pass over the
   single decode row, unioned over rows for micro-batches),
2. intersects them with the weight-side schedule to produce a per-slot
   survival mask of planes (:func:`work_mask`) the Pallas kernel consumes
   as a scalar-prefetch operand, and
3. accounts executed vs weight-only tile-dots (:func:`record_skip` /
   :func:`skip_stats`) so ``latency_stats()`` and the bench can report
   ``act_skip_frac`` honestly.

Bit-exactness argument (why masking cannot change the output): the mask only
*drops* K-tile slots whose activation slice is identically zero, and their
passes would have added exactly ``+0.0`` to their f32 segment accumulators.
Adding 0.0 is a bitwise no-op on every finite f32 (and on the parity tests'
``assert_array_equal``, where ``-0.0 == +0.0``), and surviving slots keep
their ascending K order, so per-segment accumulation sequences are
operation-for-operation identical to the unmasked walk.  Hence
``pallas(skip) == pallas == planes`` bit-for-bit — the property wall in
``tests/test_schedule.py`` pins all three.

The packed-word form (:func:`intersect_packed_presence`) is the metadata
view of the same intersection: weight presence words AND the broadcast
activation presence words, per bit plane — its popcount equals the work
surviving the mask, which the property tests also pin.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplanes

__all__ = [
    "ktile_presence",
    "work_mask",
    "mask_passes",
    "intersect_packed_presence",
    "record_skip",
    "skip_stats",
    "reset_skip_stats",
    "GEMV_ROWS_MAX",
]

# The decode-GEMV gate: activation skip engages only when the flattened
# activation has at most this many rows (one f32 sublane).  A decode step is
# M = batch <= 8 here; prefill (M = batch * seq) falls back to the static
# weight-only schedule — a union of presence over hundreds of rows is all
# ones, so masking would add runtime cost for zero skipped work.
GEMV_ROWS_MAX = 8


def ktile_presence(a: jax.Array, ks: int) -> jax.Array:
    """Per-K-tile activation presence: int32 [K // ks] in {0, 1}.

    ``presence[t] = any(a[:, t*ks:(t+1)*ks] != 0)`` — the union over the
    (GEMV-few) rows of ``a``.  A tile is absent only when *every* row's
    slice is zero, which is exactly the condition under which dropping the
    tile's passes is a bitwise no-op for every row of the output.

    ``a`` must already be padded to the stored (tile-aligned) K; padding
    columns are zero and never flip a presence bit.
    """
    m, k = a.shape
    if k % ks:
        raise ValueError(f"activation K {k} not divisible by ks={ks}")
    tiles = a.reshape(m, k // ks, ks)
    return jnp.any(tiles != 0, axis=(0, 2)).astype(jnp.int32)


def work_mask(plane_bits: jax.Array, ktile_ids: jax.Array,
              act_presence: jax.Array) -> jax.Array:
    """Survival mask over schedule slots: int32 [n_tiles, num_work] of plane
    masks (any leading stack or shard axes pass through).

    Slot (j, w) keeps its weight-side plane mask ``plane_bits[j, w]`` (0 on
    the padding tail) iff the activation K-tile it names is present, else
    0.  The static weight-only walk's mask is ``plane_bits`` itself.
    Monotone by construction: every surviving
    plane is a weight-side plane (work ⊆ weight-only work), and surviving
    slots keep their positions, preserving the per-segment f32
    accumulation order the bit-exactness proof needs.
    """
    return jnp.where(act_presence[ktile_ids] != 0, plane_bits, 0)


def mask_passes(mask: jax.Array) -> jax.Array:
    """MXU passes a survival mask runs: the popcount of every slot's plane
    mask, summed (int32 scalar)."""
    return jnp.sum(bitplanes.popcount(mask))


def intersect_packed_presence(occupancy: jax.Array,
                              act_presence: jax.Array) -> jax.Array:
    """AND activation presence into the weight-side packed presence words.

    ``occupancy`` is the kneaded format's uint32 [B-1, ceil(nk/32), NN]
    pass-mark metadata (1 bit per (plane, K-tile, N-tile)); the activation
    contributes one bit per K-tile, broadcast over planes and N-tiles.
    Returns the intersected words, same shape/dtype.  Its per-(plane, tile)
    popcount equals the surviving work count of :func:`work_mask` — the
    metadata-level and schedule-level views of the same skip, which the
    property suite pins against each other.
    """
    nk = act_presence.shape[0]
    act_words = bitplanes.pack_presence(
        act_presence.reshape(1, nk, 1))          # [1, ceil(nk/32), 1]
    return occupancy & act_words


# ---------------------------------------------------------------------------
# Skip accounting — executed vs weight-only tile-dots, per process
# ---------------------------------------------------------------------------
# The counters live module-level because the interesting callers are jitted
# (the engine's decode step): a ``jax.debug.callback`` fires at *runtime*
# inside the traced computation and folds each launch's (executed,
# weight-only) pair into this accumulator.  Engines snapshot at init and
# report deltas, so concurrent engines see their own traffic plus any
# overlapping peer's — fine for serving stats, and the tests use
# :func:`reset_skip_stats` for exact accounting.

_LOCK = threading.Lock()
_EXECUTED = 0
_WEIGHT_ONLY = 0
_CALLS = 0


def _accumulate(executed, weight_only) -> None:
    global _EXECUTED, _WEIGHT_ONLY, _CALLS
    with _LOCK:
        _EXECUTED += int(np.asarray(executed))
        _WEIGHT_ONLY += int(np.asarray(weight_only))
        _CALLS += 1


def record_skip(mask: jax.Array, plane_bits: jax.Array) -> None:
    """Fold one masked launch into the process-wide skip counters.

    Call inside the jitted wrapper, right where the mask is built:
    ``executed = mask_passes(mask)`` (surviving tile-dots this launch) and
    ``weight_only = mask_passes(plane_bits)`` (what the static schedule
    would have run).  Shapes are static so the sums fuse into the step; the
    callback is the only host hop and fires once per launch.
    """
    jax.debug.callback(_accumulate, mask_passes(mask),
                       mask_passes(plane_bits))


def skip_stats() -> Dict[str, float]:
    """Snapshot of the process-wide skip counters.

    Returns ``executed_tile_dots``, ``weight_tile_dots``, ``skip_calls``
    and the derived ``act_skip_frac = 1 - executed / weight_only`` (0.0
    when nothing was recorded).  Flushes pending debug callbacks first so a
    read after ``drain()`` sees every decode step's launch.
    """
    jax.effects_barrier()
    with _LOCK:
        executed, weight_only, calls = _EXECUTED, _WEIGHT_ONLY, _CALLS
    frac = 1.0 - executed / weight_only if weight_only else 0.0
    return {
        "executed_tile_dots": executed,
        "weight_tile_dots": weight_only,
        "skip_calls": calls,
        "act_skip_frac": frac,
    }


def reset_skip_stats() -> None:
    """Zero the process-wide counters (test isolation)."""
    global _EXECUTED, _WEIGHT_ONLY, _CALLS
    jax.effects_barrier()
    with _LOCK:
        _EXECUTED = 0
        _WEIGHT_ONLY = 0
        _CALLS = 0
