"""Pallas TPU kernel: SAC bit-plane matmul on a compacted work schedule.

Hardware mapping of the paper's PE (Fig 5) onto the TPU memory hierarchy:

  throttle buffer + pass marks  -> :class:`~repro.core.schedule.KneadedSchedule`
                                   — the occupancy map compacted at knead time
                                   into per-N-tile work lists of non-empty
                                   (plane, K-tile) items, delivered via scalar
                                   prefetch (SMEM).  The grid walks the lists,
                                   so slack work is never *dispatched*, rather
                                   than dispatched-and-predicated-away
  splitter array                -> in-VMEM unpack of the one bit-packed plane
                                   the current work item names (32 weights/
                                   uint32 word) + sign application
  16x16 segment adder fabric    -> one MXU dot per scheduled work item
  segment registers S0..S15     -> VMEM scratch accumulator [B-1, bm, bn] f32,
                                   indexed by the item's plane id
  rear adder tree (shift once)  -> epilogue ``sum_b 2^b * S_b`` executed once
                                   per output tile at the last work step
  per-channel scale             -> applied once in the same epilogue (SAC's
                                   "no intermediate pair-wise partial sums")

Grid: ``(M/bm, N/bn, num_work)`` with the *work list* innermost (revisiting =
output-stationary).  ``num_work`` is the max per-N-tile work count; tile j
executes exactly its surviving mask entries as MXU passes and idles through
the rest — padded schedule entries repeat the tile's last real item, so their
index maps request already-resident blocks and Pallas elides the DMA.  The
guard consults a scalar-prefetched *survival mask* rather than the raw work
counts: the static weight-only mask (``w < counts[j]`` expanded per slot)
reproduces the original walk bit-for-bit, while the runtime
activation-intersected mask (docs/DESIGN.md §12) additionally drops real
items whose activation K-slice is all zero — the two-sided skip.  Total
executed MXU passes per M-step therefore equal the *intersected* occupancy
nonzero count, not the dense ``(B-1) * K/bk * N/bn`` — the paper's "skip the
slack" realized at the front-end scheduler rather than in the kernel body.

Work items are k-major (K-tile ascending, plane within), so consecutive items
share the activation and sign blocks, and per-plane segments accumulate their
K-tiles in ascending order — the same accumulation sequence as a dense K
sweep, which keeps this kernel bit-exact against the planes oracle.

Multi-device (docs/DESIGN.md §5): the grid's N dimension partitions across a
mesh by sharding the *schedule* — ``ops.sac_matmul_pallas_sharded`` launches
this same kernel under ``jax.shard_map`` with each device holding a
contiguous slab of N-tiles plus exactly those tiles' work lists
(``ShardedKneadedWeight``), so per-device executed MXU passes equal the
shard's occupancy nonzeros and per-tile accumulation order — hence
bit-exactness — is preserved shard by shard.

``bk`` equals the kneading stride KS — the skip-granularity trade-off the
paper sweeps in Fig 11.  Larger KS: fewer, coarser skip chances but less
metadata; smaller KS: finer skips, more metadata.  With packed presence bits
(1 bit per (plane, K-tile, N-tile)) plus the int32 schedule (a count per
N-tile + 2 words per work slot, slots = N-tiles x the *max* per-tile
occupied count), metadata scales with the worst occupied N-tile rather than
the dense tile count, so small-KS schedules on sparse weights stay cheap.

VMEM budget per step (bm=bn=256, bk=512, B=8):
  A tile 256x512x4B = 512KB; one plane tile (512/32)x256x4B = 16KB;
  segment scratch 7x256x256x4B = 1.8MB; sign-multiplier cache
  512x256x4B = 512KB; out 256KB  => ~3.1MB << VMEM.
(The dense-grid kernel staged all B-1 plane tiles per step; the schedule
names one plane per item, cutting the staged plane footprint (B-1)x.)
MXU alignment: bm, bn multiples of 128; bk multiple of 256 (>= 8 sublanes of
packed words after the x32 unpack).

Decode / GEMV regime (LM serving, M = batch, often 1): the same kernel runs
with ``bm`` shrunk to the 8-row f32 sublane floor — the ops-layer
``_pad_activations`` rounds M up to a multiple of 8 and caps the M block at
that, so a one-token decode step is a single M-step grid whose A tile is
8 x bk instead of a 97%-padding 256-row slab.  The work-list walk, segment
scratch indexing, and epilogue are identical to the streamed prefill grid;
only the block shape changes, so decode output stays bit-exact against the
planes oracle (and therefore against prefill logits for the same row).
``bm`` must stay a multiple of 8 (sublane floor) — asserted below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import KneadedSchedule
from repro.kernels.backend import exact_dot_precision

WORD = 32


def _unpack_words(words: jax.Array, bk: int) -> jax.Array:
    """[bk//32, bn] uint32 -> [bk, bn] int32 {0,1} (little-endian per word).

    Unpacks in int32: Mosaic has no uint32 -> f32 cast, and an arithmetic
    right shift leaves bit 0 equal to the selected bit all the same."""
    nw, bn = words.shape
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (nw, WORD, bn), 1)
    bits = (words[:, None, :] >> shifts) & 1
    return bits.reshape(nw * WORD, bn)


def sac_matmul_kernel(
    mask_ref,       # scalar prefetch: [N/bn, num_work] int32 survival mask
    plane_ids_ref,  # scalar prefetch: [N/bn, num_work] int32
    ktile_ids_ref,  # scalar prefetch: [N/bn, num_work] int32
    a_ref,          # [bm, bk] activations (block of the scheduled K-tile)
    plane_ref,      # [1, bk//32, bn] uint32 — the scheduled plane, packed
    signs_ref,      # [bk//32, bn] uint32 packed sign bits
    scale_ref,      # [1, bn] f32 per-channel scales
    out_ref,        # [bm, bn] f32
    seg_ref,        # VMEM scratch: [B-1, bm, bn] f32 segment accumulators
    signf_ref,      # VMEM scratch: [bk, bn] f32 cached sign multiplier
    last_kt_ref,    # SMEM scratch: [1] int32 K-tile the sign cache holds
    *,
    bits: int,
    num_work: int,
):
    j = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        seg_ref[...] = jnp.zeros_like(seg_ref)
        last_kt_ref[0] = -1                # invalidate the sign cache

    @pl.when(mask_ref[j, w] != 0)          # surviving work item (else idle)
    def _mxu_pass():
        b = plane_ids_ref[j, w]            # segment register select
        kt = ktile_ids_ref[j, w]
        a = a_ref[...].astype(jnp.float32)

        # k-major order makes consecutive items share the (K-tile, N-tile)
        # sign block: unpack the {-1,+1} multiplier once per K-tile change,
        # not once per plane item (j is fixed within a tile's work walk, so
        # the K-tile id alone keys the cache).
        @pl.when(kt != last_kt_ref[0])
        def _refresh_sign_cache():
            sign_bits = _unpack_words(signs_ref[...], a.shape[1])
            # sign multiplier in {-1, +1}: 1 - 2*bit
            signf_ref[...] = 1.0 - 2.0 * sign_bits.astype(jnp.float32)
            last_kt_ref[0] = kt

        plane = _unpack_words(plane_ref[0], a.shape[1]).astype(jnp.float32)
        seg_ref[b] += jax.lax.dot_general(
            a, plane * signf_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=exact_dot_precision(a_ref.dtype),
            preferred_element_type=jnp.float32,
        )

    @pl.when(w == num_work - 1)
    def _rear_adder_tree():
        # Single shift-and-add over segments + single dequant scale (SAC),
        # summed in plane order with Python-float weights (exact powers of
        # two) — the order ``core.sac.sac_matmul_planes`` replays.
        acc = seg_ref[0]
        for b in range(1, bits - 1):
            acc = acc + seg_ref[b] * float(2 ** b)
        out_ref[...] = acc * scale_ref[...]


def sac_matmul_pallas_call(
    a: jax.Array,
    planes: jax.Array,
    signs: jax.Array,
    scale: jax.Array,
    schedule: KneadedSchedule,
    *,
    bits: int,
    bm: int = 256,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = True,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Raw pallas_call wrapper (shapes must already be tile-aligned).

    ``mask`` is the per-slot survival mask, int32 [N/bn, num_work] — the
    *runtime* half of the two-sided skip (docs/DESIGN.md §12).  ``None``
    (the static weight-only walk) expands the schedule counts to the mask
    the pre-skip guard ``w < counts[j]`` tested, so the masked kernel is
    bit-for-bit the unmasked one.  An activation-intersected mask may
    additionally drop real items whose activation K-slice is all zero;
    surviving items keep their k-major slot positions, so per-segment f32
    accumulation order — hence bit-exactness vs the planes oracle — is
    preserved.
    """
    m, k = a.shape
    n = planes.shape[-1]
    assert bm % 8 == 0, f"bm={bm} must be a multiple of the 8-row sublane floor"
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert schedule.nk == k // bk and schedule.n_tiles == n // bn, (
        schedule.nk, schedule.n_tiles, k // bk, n // bn)
    num_work = schedule.num_work
    grid = (m // bm, n // bn, num_work)
    if mask is None:
        from repro.core.activation_occupancy import weight_only_mask
        mask = weight_only_mask(schedule.counts, num_work)
    assert mask.shape == schedule.plane_ids.shape, (
        mask.shape, schedule.plane_ids.shape)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        # NB: with scalar prefetch, index maps receive the prefetch refs
        # last; they *walk the schedule* — block indices come from the work
        # lists, not from the grid coordinates.
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, w, msk, pid, kid: (i, kid[j, w])),
            pl.BlockSpec((1, bk // WORD, bn),
                         lambda i, j, w, msk, pid, kid: (pid[j, w],
                                                         kid[j, w], j)),
            pl.BlockSpec((bk // WORD, bn),
                         lambda i, j, w, msk, pid, kid: (kid[j, w], j)),
            pl.BlockSpec((1, bn), lambda i, j, w, msk, pid, kid: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, w, msk, pid, kid: (i, j)),
        scratch_shapes=[pltpu.VMEM((bits - 1, bm, bn), jnp.float32),
                        pltpu.VMEM((bk, bn), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(sac_matmul_kernel, bits=bits,
                               num_work=num_work)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="sac_matmul",
    )(mask.astype(jnp.int32), schedule.plane_ids, schedule.ktile_ids,
      a, planes, signs, scale)
