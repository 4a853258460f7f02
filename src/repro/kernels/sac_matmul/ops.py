"""Jitted public wrappers for the SAC bit-plane Pallas kernel.

``sac_matmul_pallas``: the raw [M, K] x kneaded [K, N] op — padding/tiling
policy and backend dispatch (compiled Pallas on TPU, interpret mode on CPU,
an error anywhere else — ``repro.kernels.backend``).  Accepts activations
sized to either the stored (tile-aligned) or the logical reduction dim and
zero-pads internally — padded rows meet all-zero weight rows that the
schedule never dispatches.

``sac_conv2d``: the batched convolution entry point — im2col + schedule-
compacted SAC matmul behind **one** ``pallas_call``: the kernel grid's M
dimension streams every activation row of the [B*H'*W', K] patch matrix
through VMEM one [bm, bk] slab per M-step.  No host-side slab loop, no
remainder-shape retraces, no concatenate — a VGG-16-sized patch matrix costs
one launch whose peak VMEM footprint is still a single block.

``sac_matmul_pallas_sharded``: the multi-device form (docs/DESIGN.md §5,
§8) — the same kernel launched under ``jax.shard_map`` over a mesh axis,
one launch per device, each device walking *its own shard's* compacted work
list (a :class:`~repro.core.schedule.ShardedKneadedWeight`, or a per-layer
scan slice of a stacked LM
:class:`~repro.core.schedule.ShardedStackedKneadedWeight`).  Kneaded MoE
expert banks take a different route entirely: whole experts live on the
"expert" mesh axis and each expert's 2-D slice reaches ``sac_matmul_pallas``
through the block-level ``lax.scan`` (docs/DESIGN.md §13) — banks never
enter the sharded N-split entry here.  Activations
are replicated, outputs concatenate along N with no collective in the
matmul itself; per-device executed MXU passes equal that shard's occupancy
nonzeros.  The GEMV decode fast path survives sharding: ``_pad_activations``
shrinks the M block *before* the shard_map, so a batch-1 LM decode step
runs a single 8-row M-step per device rather than a 97%-padding streamed
slab.  ``sac_conv2d``, the FC dispatch, and ``core.sac.sac_matmul`` (the
LM projection entry) all accept sharded weights with a ``mesh``;
``mesh=None`` runs the shards serially on one device — the oracle the
multi-device parity tests compare against.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import activation_occupancy
from repro.core.kneading import KneadedWeight, ShardedKneadedWeight
from repro.core.schedule import KneadedSchedule
from repro.kernels.backend import interpret_mode
from repro.kernels.sac_matmul.kernel import sac_matmul_pallas_call

# Most rows in one M block of a matmul launch.  A grid step's cost is
# largely fixed (stage one K-tile, pipeline the next), whatever its M, so a
# prefill-sized launch walks its K-tile list once per 1024 rows rather than
# once per 256 (segment scratch at 1024 rows: 7 x 1024 x 128 f32 = 3.7 MB
# of VMEM).  ``sac_conv2d`` keeps 256-row blocks.
MATMUL_BM = 1024


@functools.partial(
    jax.jit, static_argnames=("bits", "ks", "n_block", "bm", "interpret"))
def _run(a, planes, signs, scale, schedule, mask, *, bits, ks, n_block, bm,
         interpret):
    return sac_matmul_pallas_call(
        a, planes, signs, scale, schedule,
        bits=bits, bm=bm, bn=n_block, bk=ks,
        interpret=interpret, mask=mask,
    )


def sac_matmul_pallas(
    a: jax.Array,
    kw: KneadedWeight,
    *,
    bm: int = MATMUL_BM,
    skip_activations: bool = False,
) -> jax.Array:
    """[M, K] @ kneaded [K, N] -> [M, N] f32 via the Pallas SAC kernel.

    M is padded up to the tile size.  K may be either the stored (aligned)
    ``kw.k`` or the logical ``kw.logical_k`` — logical activations are
    zero-padded here, exactly as ``sac_conv2d`` does, so direct FC callers
    need no padding logic of their own.  N alignment is guaranteed by the
    kneaded format (n_block | N); the output keeps the stored N (slice to
    ``kw.logical_n`` at the call site if needed).

    ``skip_activations=True`` arms the two-sided skip (docs/DESIGN.md §12):
    per-K-tile presence bits computed from the (padded) activations are
    intersected into the schedule walk via the kernel's survival mask, so
    occupied K-tiles whose activation slice is all zero never execute an
    MXU pass.  Bit-exact against the unskipped walk — a dropped pass would
    have contributed exactly 0.0 to its f32 segment, and surviving slots
    keep their ascending K order.  ``core.sac.sac_matmul`` gates this to the
    decode-GEMV regime; this raw entry applies it at any M when asked.

    The kernel itself is strictly 2-D: stacked weights (LM layer stacks,
    MoE expert banks — planes ndim > 3) must be sliced to one [K, N]
    kneaded weight per call (``lax.scan`` over the stack axes, as
    ``models.blocks._dispatch_compute_kneaded`` does for expert banks;
    docs/DESIGN.md §13).
    """
    if kw.planes.ndim > 3:
        raise ValueError(
            f"sac_matmul_pallas is a 2-D [K, N] kernel; got stacked planes "
            f"{kw.planes.shape} — scan/index the leading stack axes down to "
            f"one slice first (expert banks: models.blocks."
            f"_dispatch_compute_kneaded, docs/DESIGN.md §13)")
    a, m, bm_eff = _pad_activations(a, kw, bm)
    mask = kw.schedule.plane_bits
    if skip_activations:
        presence = activation_occupancy.ktile_presence(a, kw.ks)
        mask = activation_occupancy.work_mask(
            mask, kw.schedule.ktile_ids, presence)
        activation_occupancy.record_skip(mask, kw.schedule.plane_bits)
    out = _run(
        a, kw.planes, kw.signs, kw.scale, kw.schedule, mask,
        bits=kw.bits, ks=kw.ks, n_block=kw.n_block, bm=bm_eff,
        interpret=interpret_mode(),
    )
    return out[:m]


def m_block(m: int, bm: int = MATMUL_BM) -> int:
    """Effective M block for an M-row launch — the decode/GEMV fast path:
    M rounded up to the 8-row f32 sublane floor, capped at ``bm``.  Shared
    with the planes oracle (``core.sac``), which replays the kernel at the
    same padded M so odd-M launches stay bit-comparable (XLA CPU picks
    different dense-matmul micro-kernels for e.g. M=7 vs M=8 at wide N —
    the same reduction-order sensitivity docs/DESIGN.md §5 records for
    forced host devices)."""
    return min(bm, max(8, -(-m // 8) * 8))


def _pad_activations(a: jax.Array, kw, bm: int):
    """The M/K padding policy shared by the unsharded and sharded entry
    points: accept logical-K activations (zero-pad to the stored dim — the
    padded rows meet all-zero weight rows the schedule never dispatches)
    and round M up to the effective block size.

    The M-block shrinks to fit tiny batches — the decode/GEMV fast path:
    ``bm_eff = min(bm, M rounded up to the 8-row f32 sublane floor)``, so an
    M=1 decode step pads one row to 8 and runs a single M-step instead of
    padding to a full streaming block (31/32 of every A-tile DMA and MXU
    pass would be padding).  Prefill and conv calls (M >= bm) keep
    the full streamed grid.  ``bm_eff`` is always a multiple of 8, so
    mid-size M (e.g. 12) pads to an aligned single block rather than
    running a misaligned one.
    """
    m, k = a.shape
    if k != kw.k:
        if k != kw.logical_k:
            raise ValueError(f"activation K {k} matches neither stored "
                             f"{kw.k} nor logical {kw.logical_k}")
        a = jnp.pad(a, ((0, 0), (0, kw.k - k)))
    bm_eff = m_block(m, bm)
    pad = (-m) % bm_eff
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
    return a, m, bm_eff


def sac_matmul_pallas_sharded(
    a: jax.Array,
    skw: ShardedKneadedWeight,
    mesh=None,
    axis: str = "model",
    *,
    bm: int = MATMUL_BM,
    skip_activations: bool = False,
) -> jax.Array:
    """[M, K] @ N-sharded kneaded [K, N] -> [M, N] f32, one kernel per shard.

    With a ``mesh``, runs under ``jax.shard_map`` over ``axis``: activations
    replicated, every weight/schedule array split on its leading shard dim,
    each device launching the SAC kernel on its own compacted work list and
    writing its [M, N/S] output slab — the outputs concatenate along N
    (``out_specs=P(None, axis)``), so the matmul itself needs no collective.
    All shards run the same program: the work-dim extent is the *global*
    ``num_work`` and per-shard ragged tails idle exactly like ragged N-tiles
    do on one device.

    With ``mesh=None``, executes the shards serially on the local device and
    concatenates — bit-identical output (each shard's N-tiles keep their
    single-device K-tile walks), used as the parity oracle
    and for host-side analysis without a mesh.

    ``partition="balanced"`` weights (docs/DESIGN.md §11) come out of the
    per-device kernels in *packed slot order* — the LPT bin-packing moved
    whole N-tiles between shards.  The epilogue gathers the [m, n_block]
    output blocks back into original column order through ``skw.tile_slot``
    (``out_tile[j] = packed_tile[tile_slot[j]]``).  Each tile's value was
    produced by the same passes in the same K order as on one device, so
    the gathered output is bit-exact against the unsharded
    kernel; for a mesh run the gather is the only cross-shard data movement
    the op introduces.

    Output keeps the sharded stored N (slice to ``skw.logical_n`` at the
    call site, as with the unsharded op).

    ``skip_activations=True``: the activation K-tile presence is computed
    *once* from the replicated (padded) activations — sharding is along N,
    so every shard sees the same presence bits — and intersected with each
    shard's own plane masks into a per-shard survival mask [S, T,
    num_work], sliced per device alongside the schedule arrays.  The
    balanced partition's ``tile_slot`` gather epilogue is untouched:
    masking changes which passes a tile executes, never which shard/slot
    the tile lives in.
    """
    interpret = interpret_mode()
    a, m, bm_eff = _pad_activations(a, skw, bm)
    # per-slot survival masks, one row of shards: [S, T, num_work]
    mask = skw.plane_bits
    if skip_activations:
        presence = activation_occupancy.ktile_presence(a, skw.ks)
        mask = activation_occupancy.work_mask(mask, skw.ktile_ids, presence)
        activation_occupancy.record_skip(mask, skw.plane_bits)

    def one_shard(a_, planes, signs, scale, counts, kids, pbits, mask_):
        # inside shard_map every arg holds this device's slab with the
        # leading shard axis collapsed to extent 1
        sched = KneadedSchedule(
            counts=counts[0], ktile_ids=kids[0], plane_bits=pbits[0],
            num_work=skw.num_work, total_work=skw.total_work,
            nk=skw.nk, n_tiles=skw.tiles_per_shard)
        return sac_matmul_pallas_call(
            a_, planes[0], signs[0], scale[0], sched,
            bits=skw.bits, bm=bm_eff, bn=skw.n_block, bk=skw.ks,
            interpret=interpret, mask=mask_[0])

    if mesh is None:
        outs = [one_shard(a, skw.planes[s:s + 1], skw.signs[s:s + 1],
                          skw.scale[s:s + 1], skw.counts[s:s + 1],
                          skw.ktile_ids[s:s + 1], skw.plane_bits[s:s + 1],
                          mask[s:s + 1])
                for s in range(skw.num_shards)]
        out = jnp.concatenate(outs, axis=1)
    else:
        sharded = (P(axis),) * 7
        out = jax.shard_map(
            one_shard, mesh=mesh, in_specs=(P(),) + sharded,
            out_specs=P(None, axis), check_vma=False,
        )(a, skw.planes, skw.signs, skw.scale, skw.counts,
          skw.ktile_ids, skw.plane_bits, mask)
    if skw.partition == "balanced":
        tiles = out.reshape(out.shape[0], -1, skw.n_block)
        out = jnp.take(tiles, skw.tile_slot, axis=1
                       ).reshape(out.shape[0], -1)
    return out[:m]


def im2col(x: jax.Array, k: int, stride: int) -> jax.Array:
    """x [B, H, W, C] -> patches [B, H', W', C*k*k] ('SAME' padding).

    The single source of truth for the conv lowering — the float path in
    ``models/cnn.py`` imports this same function, so float and kneaded
    convolutions see identical patch layouts by construction.

    The patches are a convolution with a one-hot kernel, which a TPU runs
    on the MXU: at default precision that rounds f32 activations to bf16,
    so the copy asks for full precision to stay exact.
    """
    return jax.lax.conv_general_dilated_patches(
        x, (k, k), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def sac_conv2d(
    x: jax.Array,
    kw,
    *,
    ksize: int,
    stride: int = 1,
    bias: Optional[jax.Array] = None,
    impl: str = "pallas",
    bm: int = 256,
    mesh=None,
    axis: str = "model",
) -> jax.Array:
    """2-D convolution as im2col + SAC matmul against a kneaded filter.

    The filter is the kneaded form of the [C*kh*kw, out_ch] im2col weight
    matrix (use ``knead_padded`` — C*k*k is rarely tile-aligned).  For
    ``impl="pallas"`` the whole [B*H'*W', K] patch matrix goes through a
    *single* ``pallas_call``: the grid's M dimension streams the rows in
    [bm, bk] blocks, so one launch covers the layer and the VMEM-side
    footprint stays one block regardless of image size.  Other impls
    ("planes"/"int"/"float") take the pure-jnp SAC paths — same math, used
    as oracles and fast CPU fallbacks.

    A :class:`~repro.core.schedule.ShardedKneadedWeight` filter routes
    through :func:`sac_matmul_pallas_sharded` (one kernel launch per mesh
    device, each walking its own shard's work list; ``mesh=None`` = serial
    oracle).  Sharded weights are a Pallas-path artifact, so ``impl`` must
    be "pallas" for them.

    Returns [B, H', W', out_ch] f32 (+ bias if given).
    """
    patches = im2col(x, ksize, stride)                  # [B, H', W', C*k*k]
    lead = patches.shape[:-1]
    a = patches.reshape(-1, patches.shape[-1])
    k0 = a.shape[1]
    if k0 not in (kw.k, kw.logical_k):
        raise ValueError(f"patch K {k0} does not match kneaded weight "
                         f"(stored {kw.k}, logical {kw.logical_k})")
    if isinstance(kw, ShardedKneadedWeight):
        if impl != "pallas":
            raise ValueError("sharded kneaded weights execute through the "
                             f"Pallas kernel only, got impl={impl!r}")
        out = sac_matmul_pallas_sharded(a, kw, mesh, axis, bm=bm)
        out = out[:, :kw.logical_n]
    elif impl != "pallas":
        from repro.core.sac import sac_matmul
        out = sac_matmul(a.astype(jnp.float32), kw, impl=impl)
    else:
        out = sac_matmul_pallas(a, kw, bm=bm)
        out = out[:, :kw.logical_n]
    out = out.reshape(lead + (kw.logical_n,)).astype(jnp.float32)
    if bias is not None:
        out = out + bias
    return out
