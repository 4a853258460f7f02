"""Pallas TPU kernel: SAC bit-plane matmul on a compacted K-tile walk.

Hardware mapping of the paper's PE (Fig 5) onto the TPU memory hierarchy:

  throttle buffer + pass marks  -> :class:`~repro.core.schedule.KneadedSchedule`
                                   — the occupancy map compacted at knead time
                                   into per-N-tile walks of *occupied* K-tiles,
                                   each with a mask of its non-empty planes,
                                   delivered via scalar prefetch (SMEM).  The
                                   grid walks the lists, so an empty K-tile is
                                   never *dispatched*, rather than
                                   dispatched-and-predicated-away
  splitter array                -> in-VMEM unpack of each present plane of the
                                   staged K-tile (32 weights/uint32 word) +
                                   sign application
  16x16 segment adder fabric    -> one MXU dot per present plane
  segment registers S0..S15     -> VMEM scratch accumulator [B-1, bm, bn] f32,
                                   one segment per plane
  rear adder tree (shift once)  -> epilogue ``sum_b 2^b * S_b`` executed once
                                   per output tile at the last work step
  per-channel scale             -> applied once in the same epilogue (SAC's
                                   "no intermediate pair-wise partial sums")

Grid: ``(M/bm, N/bn, num_work)`` with the *K-tile walk* innermost
(revisiting = output-stationary).  ``num_work`` is the max occupied K-tile
count of any N-tile.  One grid step stages the whole plane stack of its
K-tile — one DMA of ``(B-1, bk/32, bn)`` packed words, 28 KB at 8 bits —
with its activation and sign blocks, unpacks the signs once, and runs an
unrolled ``for b in range(B-1)`` whose body (unpack plane ``b``, apply the
signs, one MXU dot, add into ``S_b``) executes under bit ``b`` of the
slot's scalar-prefetched *survival mask*.  Padded slots repeat the tile's
last K-tile with mask 0: their index maps request already-resident blocks
(Pallas elides the DMA) and the body idles.  The static mask is the
schedule's ``plane_bits``; the runtime activation-intersected mask
(docs/DESIGN.md §12) additionally zeroes slots whose activation K-slice is
all zero — the two-sided skip.  Total executed MXU passes per M-step
therefore equal the *intersected* occupancy nonzero count, not the dense
``(B-1) * K/bk * N/bn``, while grid steps equal occupied K-tiles — the
paper's "skip the slack" realized at the front-end scheduler, with the
per-step overhead paid once per K-tile rather than once per plane.

Skip granularity, DMA versus compute: an empty K-tile (every plane empty in
this N-tile) costs neither a step nor a DMA.  An occupied K-tile's empty
planes are DMA'd with its plane stack but never unpacked or multiplied —
the pass count is exact, the bytes are the K-tile's.

Each segment ``S_b`` accumulates its K-tiles in ascending order through the
same ``[bm, bk] x [bk, bn]`` dot at the same precision as a dense K sweep;
the order of planes within one step is free because they feed separate
accumulators.  That keeps this kernel bit-exact against the planes oracle.

Multi-device (docs/DESIGN.md §5): the grid's N dimension partitions across a
mesh by sharding the *schedule* — ``ops.sac_matmul_pallas_sharded`` launches
this same kernel under ``jax.shard_map`` with each device holding a
contiguous slab of N-tiles plus exactly those tiles' walks
(``ShardedKneadedWeight``), so per-device executed MXU passes equal the
shard's occupancy nonzeros and per-tile accumulation order — hence
bit-exactness — is preserved shard by shard.

``bk`` equals the kneading stride KS — the skip-granularity trade-off the
paper sweeps in Fig 11.  Larger KS: fewer, coarser skip chances but less
metadata; smaller KS: finer skips, more metadata.  With packed presence bits
(1 bit per (plane, K-tile, N-tile)) plus the int32 schedule (a count per
N-tile + 2 words per slot, slots = N-tiles x the *max* per-tile occupied
K-tile count), metadata scales with the worst occupied N-tile rather than
the dense tile count, so small-KS schedules on sparse weights stay cheap.

VMEM budget per step (B=8, bk=256, bn=128), each input double-buffered:
  A tile bm x 256 (bf16 64 KB at bm=128, f32 1 MB at bm=1024); plane stack
  7 x 8 x 128 x 4B = 28 KB; signs 4 KB; segment scratch 7 x bm x 128 x 4B
  (3.7 MB at bm=1024); out bm x 128 x 4B; plus the unpacked sign multiplier
  (128 KB) and one plane's temporaries (128 KB unpacked + the bm x 128 dot)
  per unrolled plane.  Well inside the v5e's scoped VMEM at every block the
  ops layer uses.
MXU alignment: bm a multiple of 8, bn of 128; bk a multiple of 256 (>= 8
sublanes of packed words after the x32 unpack).

Decode / GEMV regime (LM serving, M = batch, often 1): the same kernel runs
with ``bm`` shrunk to the 8-row f32 sublane floor — the ops-layer
``_pad_activations`` rounds M up to a multiple of 8 and caps the M block at
that, so a one-token decode step is a single M-step grid whose A tile is
8 x bk instead of a 97%-padding 256-row slab.  The walk, segment scratch
indexing, and epilogue are identical to the streamed prefill grid; only the
block shape changes, so decode output stays bit-exact against the planes
oracle (and therefore against prefill logits for the same row).  ``bm``
must stay a multiple of 8 (sublane floor) — asserted below.
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
    mask_ref,       # scalar prefetch: [N/bn, num_work] int32 plane masks
    ktile_ids_ref,  # scalar prefetch: [N/bn, num_work] int32
    a_ref,          # [bm, bk] activations (block of the slot's K-tile)
    planes_ref,     # [B-1, bk//32, bn] uint32 — the K-tile's plane stack
    signs_ref,      # [bk//32, bn] uint32 packed sign bits
    scale_ref,      # [1, bn] f32 per-channel scales
    out_ref,        # [bm, bn] f32
    seg_ref,        # VMEM scratch: [B-1, bm, bn] f32 segment accumulators
    *,
    bits: int,
    num_work: int,
):
    j = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        seg_ref[...] = jnp.zeros_like(seg_ref)

    live = mask_ref[j, w]                  # this slot's surviving planes

    @pl.when(live != 0)                    # occupied K-tile (else idle)
    def _ktile():
        a = a_ref[...].astype(jnp.float32)
        bk = a.shape[1]
        # sign multiplier in {-1, +1}: 1 - 2*bit, shared by every plane
        signf = 1.0 - 2.0 * _unpack_words(signs_ref[...], bk).astype(
            jnp.float32)
        for b in range(bits - 1):

            @pl.when(((live >> b) & 1) != 0)
            def _mxu_pass(b=b):
                plane = _unpack_words(planes_ref[b], bk).astype(jnp.float32)
                seg_ref[b] += jax.lax.dot_general(
                    a, plane * signf,
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

    ``mask`` is the per-slot survival mask of planes, int32 [N/bn,
    num_work] — the *runtime* half of the two-sided skip (docs/DESIGN.md
    §12).  ``None`` runs the static weight-only walk, the schedule's own
    ``plane_bits``.  An activation-intersected mask may additionally zero
    slots whose activation K-slice is all zero; surviving slots keep their
    positions, so per-segment f32 accumulation order — hence bit-exactness
    vs the planes oracle — is preserved.
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
        mask = schedule.plane_bits
    assert mask.shape == schedule.ktile_ids.shape, (
        mask.shape, schedule.ktile_ids.shape)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        # NB: with scalar prefetch, index maps receive the prefetch refs
        # last; they *walk the schedule* — block indices come from the
        # K-tile lists, not from the grid coordinates.
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, w, msk, kid: (i, kid[j, w])),
            pl.BlockSpec((bits - 1, bk // WORD, bn),
                         lambda i, j, w, msk, kid: (0, kid[j, w], j)),
            pl.BlockSpec((bk // WORD, bn),
                         lambda i, j, w, msk, kid: (kid[j, w], j)),
            pl.BlockSpec((1, bn), lambda i, j, w, msk, kid: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, w, msk, kid: (i, j)),
        scratch_shapes=[pltpu.VMEM((bits - 1, bm, bn), jnp.float32)],
    )
    kernel = functools.partial(sac_matmul_kernel, bits=bits,
                               num_work=num_work)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="sac_matmul",
    )(mask.astype(jnp.int32), schedule.ktile_ids, a, planes, signs, scale)
