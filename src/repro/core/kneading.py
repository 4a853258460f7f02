"""Weight kneading — the paper's core contribution, in two forms.

1. **Algorithmic model** (:func:`kneaded_cycles`): the exact combinatorial
   semantics of Fig 3.  Within a group of ``ks`` (kneading stride) weights in
   a reduction lane, essential bits bubble up per bit-column independently, so
   the group compresses from ``ks`` weight-cycles to

       cycles(group) = max_b  popcount_b(group)

   (the tallest bit-column of the group).  Zero-value weights vanish for free
   (all their columns are empty) — the paper's "two orthogonal dimensions" of
   slack.  This drives the cycle-accurate cost model that reproduces the
   paper's Figs 8/10/11.

2. **TPU kneaded format** (:class:`KneadedWeight` / :func:`knead`): the
   deployable artifact — sign-magnitude bit planes, bit-packed 32/word along
   K, with per-(plane, tile) occupancy presence bits compacted into a
   :class:`~repro.core.schedule.KneadedSchedule` so the Pallas kernel
   dispatches occupied tiles *only*, and the storage footprint is
   ``bits/16`` of bf16.  Kneading is *exact*:
   ``unknead(knead(w)) == dequantize(quantize(w))`` bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import bitplanes
from repro.core.quantization import QuantizedTensor, quantize
from repro.core.schedule import (KneadedIntegrityError, KneadedSchedule,
                                 ShardedKneadedWeight,
                                 ShardedStackedKneadedWeight, build_schedule,
                                 integrity_checksums, shard_schedule,
                                 shard_stacked_schedule, tile_passes,
                                 verify_checksums)

__all__ = [
    "KNEADABLE_NAMES",
    "KneadedIntegrityError",
    "KneadedWeight",
    "ShardedKneadedWeight",
    "ShardedStackedKneadedWeight",
    "shard_schedule",
    "shard_stacked_schedule",
    "knead",
    "knead_padded",
    "knead_stacked",
    "kneadable_dims",
    "kneaded_codes",
    "reknead_like",
    "unknead",
    "kneaded_cycles",
    "kneading_ratio",
]


# Weight-name suffixes eligible for kneading / quantized serving: 2-D
# projection matrices, their stacked scan-layer forms, and MoE expert banks.
# Embeddings stay bf16 (gather path); norms/gates are not matmuls.  Single
# source of truth shared by inference.engine.knead_params and launch.specs
# (they used to carry drifting copies).
KNEADABLE_NAMES = ("wq", "wk", "wv", "wo", "wi", "wi_gate", "wi_up", "up",
                   "down", "w_in", "w_out", "in_proj", "out_proj", "unembed")


# ---------------------------------------------------------------------------
# 1. The paper-faithful kneading cycle model (Fig 3 semantics)
# ---------------------------------------------------------------------------

def kneaded_cycles(q: jax.Array, bits: int, ks: int) -> jax.Array:
    """Cycles to process each KS-group of a weight lane after kneading.

    Args:
      q:    integer codes laid out [K, ...] with K the reduction (lane) axis.
      bits: fixed-point width (B); magnitude planes are B-1.
      ks:   kneading stride — group size along K.  K % ks must be 0.
    Returns:
      int32 [K // ks, ...]: per-group kneaded cycle count,
      ``max_b popcount_b(group)``.  Un-kneaded cost is ``ks`` per group.
    """
    k = q.shape[0]
    if k % ks:
        raise ValueError(f"lane length {k} not divisible by ks={ks}")
    planes = bitplanes.magnitude_planes(q, bits)          # [B-1, K, ...]
    g = planes.reshape((planes.shape[0], k // ks, ks) + planes.shape[2:])
    counts = jnp.sum(g.astype(jnp.int32), axis=2)          # [B-1, K/ks, ...]
    return jnp.max(counts, axis=0)                         # [K/ks, ...]


def kneading_ratio(q: jax.Array, bits: int, ks: int) -> jax.Array:
    """T_ks / T_base of Fig 11: kneaded cycles over un-kneaded cycles."""
    cyc = kneaded_cycles(q, bits, ks)
    return jnp.sum(cyc) / (cyc.size * ks)


# ---------------------------------------------------------------------------
# 2. The deployable TPU kneaded-weight format
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KneadedWeight:
    """A [K, N] weight matrix in kneaded (packed bit-plane) form.

    Attributes:
      planes:    uint32 [B-1, K/32, N] — magnitude planes, bit-packed along K.
      signs:     uint32 [K/32, N]      — sign bits (1 = negative), packed.
      scale:     f32 broadcastable to [1, N] — per-output-channel scale.
      occupancy: uint32 [B-1, ceil(K/ks/32), N/n_block] — per-(plane, tile)
                 essential-bit presence, bit-packed along the K-tile axis
                 (the pass-mark metadata; see :meth:`occupancy_map`).
      schedule:  the occupancy map compacted into per-N-tile walks of
                 occupied K-tiles, each with its non-empty plane mask — what
                 the kernel actually executes (scalar-prefetched; built once
                 at knead time).
      bits:      static fixed-point width B.
      ks:        static kneading stride == kernel K-tile extent.
      n_block:   static kernel N-tile extent for occupancy granularity.
      k, n:      static *stored* (tile-aligned) dims.
      k_orig, n_orig: static logical dims before alignment padding (0 means
                 "same as stored" — the un-padded case).  Padding rows/cols
                 are all-zero codes whose occupancy is 0, so the kernel skips
                 them for free and the padded matmul is exact.

    A *stacked* kneaded weight (:func:`knead_stacked`) carries one or more
    extra leading stack axes on every array field while the statics describe
    the per-slice dims — ``jax.lax.scan`` over such a pytree slices out the
    leading axis one step at a time (a [L, E, K, N] MoE bank scans to
    per-layer [E, K, N] banks, which scan again to plain per-expert
    ``KneadedWeight``s).
    """

    planes: jax.Array
    signs: jax.Array
    scale: jax.Array
    occupancy: jax.Array
    schedule: KneadedSchedule
    bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    ks: int = dataclasses.field(metadata=dict(static=True), default=256)
    n_block: int = dataclasses.field(metadata=dict(static=True), default=128)
    k: int = dataclasses.field(metadata=dict(static=True), default=0)
    n: int = dataclasses.field(metadata=dict(static=True), default=0)
    k_orig: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_orig: int = dataclasses.field(metadata=dict(static=True), default=0)
    # knead-time per-field CRC32s ((field, crc) pairs; () = unchecked).
    # Kneading is an exact re-encoding, so a silently corrupted plane,
    # presence word, or schedule entry changes *which work executes* —
    # detection has to be byte-level, not numeric (docs/DESIGN.md §10).
    checksums: Tuple[Tuple[str, int], ...] = dataclasses.field(
        metadata=dict(static=True), default=())

    _INTEGRITY_FIELDS = ("planes", "signs", "scale", "occupancy",
                         "schedule.counts", "schedule.ktile_ids",
                         "schedule.plane_bits")

    @property
    def shape(self):
        return (self.k, self.n)

    def with_checksums(self) -> "KneadedWeight":
        """Stamp knead-time CRC32s over every array field (host-side;
        call outside jit — checksumming forces a device fetch)."""
        return dataclasses.replace(
            self, checksums=integrity_checksums(self, self._INTEGRITY_FIELDS))

    def verify(self, strict: bool = False) -> Tuple[str, ...]:
        """Names of array fields whose bytes changed since knead time.

        Returns an empty tuple when intact (or when no checksums were
        recorded — pre-integrity weights verify vacuously).  ``strict``
        raises :class:`~repro.core.schedule.KneadedIntegrityError` listing
        the corrupted fields instead of returning them.
        """
        bad = verify_checksums(self, self.checksums)
        if bad and strict:
            raise KneadedIntegrityError(
                f"kneaded weight [{self.logical_k}x{self.logical_n}] "
                f"corrupt in: {', '.join(bad)}")
        return bad

    @property
    def logical_k(self) -> int:
        """Reduction dim of the original weight (before alignment padding)."""
        return self.k_orig or self.k

    @property
    def logical_n(self) -> int:
        """Output dim of the original weight (before alignment padding)."""
        return self.n_orig or self.n

    def occupancy_map(self) -> jax.Array:
        """Unpacked presence map, int32 {0,1} [B-1, K/ks, N/n_block]."""
        return bitplanes.unpack_presence(self.occupancy, self.k // self.ks)

    def with_occupancy(self, occupancy_map: jax.Array) -> "KneadedWeight":
        """Replace the occupancy map, re-deriving packed bits + schedule.

        The kernel executes the *schedule*, so tampering with occupancy (as
        the skip-semantics tests do) must go through here to take effect.
        Checksums are re-stamped: this is a *legitimate* re-derivation, not
        corruption, so the result must verify clean.
        """
        return dataclasses.replace(
            self,
            occupancy=bitplanes.pack_presence(occupancy_map),
            schedule=build_schedule(occupancy_map),
        ).with_checksums()

    def shard(self, mesh, axis: str = "model",
              partition: str = "contiguous") -> ShardedKneadedWeight:
        """Partition this weight + schedule along N for a device mesh (one
        compacted work list per shard; see
        :func:`repro.core.schedule.shard_schedule` / docs/DESIGN.md §5).
        A stacked [L, K, N] weight (:func:`knead_stacked`) shards per layer
        into a :class:`ShardedStackedKneadedWeight` (docs/DESIGN.md §8).
        ``partition="balanced"`` LPT-packs tiles on their static occupancy
        instead of contiguous slabs (docs/DESIGN.md §11)."""
        if self.planes.ndim > 4:
            raise ValueError(
                "expert banks ([..., E, K, N] stacks) are placed on the "
                "'expert' mesh axis, not N-sharded — see docs/DESIGN.md §13")
        if self.planes.ndim == 4:
            return shard_stacked_schedule(self, mesh, axis=axis,
                                          partition=partition)
        return shard_schedule(self, mesh, axis=axis, partition=partition)

    def work_table(self):
        """Static per-slice work totals: the MXU passes of every N-tile
        (the popcount of ``schedule.plane_bits``) summed over the N-tile
        axis, as a host numpy array shaped like the stack's leading
        axes ([L, E] for an MoE bank, [L] for scan layers, scalar for a
        plain 2-D weight).  This is the ``layer_shard_work``-style input
        the routing-load / work-stealing accounting consumes: experts are
        naturally imbalanced work, and the table quantifies it without
        touching device data beyond the (tiny) plane-mask array."""
        return tile_passes(self.schedule.plane_bits).sum(axis=-1)

    def metadata_bytes(self) -> int:
        """Pass-mark metadata footprint: packed presence bits + the
        compacted schedule arrays the kernel prefetches."""
        return self.occupancy.size * 4 + self.schedule.metadata_bytes()

    def packed_bytes(self) -> int:
        """True HBM bytes of the kneaded format: packed planes + signs +
        scale + the full metadata footprint (:meth:`metadata_bytes`)."""
        return (
            self.planes.size * 4
            + self.signs.size * 4
            + self.scale.size * 4
            + self.metadata_bytes()
        )

    def dense_bf16_bytes(self) -> int:
        return self.k * self.n * 2


def kneadable_dims(k: int, n: int, ks: int = 256,
                   n_block: int = 128) -> Tuple[int, int]:
    """Smallest (K', N') >= (k, n) meeting the kneaded-format alignment:
    K' a multiple of lcm(32, ks) (bit-packing word AND kernel K tile),
    N' a multiple of n_block (kernel N tile)."""
    k_align = math.lcm(32, ks)
    return (-(-k // k_align) * k_align, -(-n // n_block) * n_block)


def knead(
    w: jax.Array,
    bits: int = 8,
    ks: int = 256,
    n_block: int = 128,
    *,
    qt: Optional[QuantizedTensor] = None,
) -> KneadedWeight:
    """Quantize (unless ``qt`` given) and knead a [K, N] weight matrix.

    K must be a multiple of lcm(32, ks); N a multiple of n_block.  Model dims
    in this framework are multiples of 128, so this holds by construction;
    for arbitrary dims (conv im2col matrices) use :func:`knead_padded`.
    """
    if qt is None:
        qt = quantize(w, bits=bits, axis=-1)
    q = qt.q
    if q.ndim != 2:
        raise ValueError(f"knead expects [K, N], got {q.shape}")
    k, n = q.shape
    if (k, n) != kneadable_dims(k, n, ks, n_block):
        raise ValueError(f"shape {q.shape} incompatible with ks={ks}, n_block={n_block}")
    mag = bitplanes.magnitude_planes(q, qt.bits)                # [B-1, K, N]
    planes = bitplanes.pack_bits(mag, axis=1)                   # [B-1, K/32, N]
    signs = bitplanes.pack_bits((q < 0).astype(jnp.uint8), axis=0)
    occ_map = bitplanes.plane_tile_occupancy(mag, ks, n_block)
    scale = qt.scale.reshape(1, -1) if qt.scale.ndim else qt.scale
    return KneadedWeight(
        planes=planes, signs=signs, scale=scale.astype(jnp.float32),
        occupancy=bitplanes.pack_presence(occ_map),
        schedule=build_schedule(occ_map),
        bits=qt.bits, ks=ks, n_block=n_block, k=k, n=n,
    ).with_checksums()


def knead_padded(
    w: jax.Array,
    bits: int = 8,
    ks: int = 256,
    n_block: int = 128,
) -> KneadedWeight:
    """Knead an arbitrarily-shaped [K, N] matrix by zero-padding to alignment.

    The conv path's im2col matrices have K = C*kh*kw (27, 576, 4800, ...),
    rarely a multiple of lcm(32, ks).  Zero padding is exact: padded rows
    multiply activations that are themselves zero-padded, padded output
    channels get scale 1.0 / codes 0 and are sliced off.  Both directions
    produce all-zero planes (occupancy 0) that the schedule never
    dispatches, so the padding costs metadata only, no MXU passes.
    ``logical_k``/``logical_n`` record the original dims for the dispatch
    layer.
    """
    if w.ndim != 2:
        raise ValueError(f"knead_padded expects [K, N], got {w.shape}")
    k0, n0 = w.shape
    kp, np_ = kneadable_dims(k0, n0, ks, n_block)
    if (kp, np_) != (k0, n0):
        w = jnp.pad(w, ((0, kp - k0), (0, np_ - n0)))
    kw = knead(w, bits=bits, ks=ks, n_block=n_block)
    if (kp, np_) == (k0, n0):
        return kw
    return dataclasses.replace(kw, k_orig=k0, n_orig=n0)


def knead_stacked(
    w: jax.Array,
    bits: int = 8,
    ks: int = 256,
    n_block: int = 128,
) -> KneadedWeight:
    """Knead a stacked weight with any leading stack axes, one slice at a
    time: [L, K, N] scan-layer weights, [E, K, N] MoE expert banks, and the
    combined [L, E, K, N] scan-layer expert banks all take this path.

    The LM stacks scan over layers with stacked params, so the serving form
    must slice per leading axis inside ``jax.lax.scan`` (an expert bank is
    sliced a second time, per local expert, inside the MoE dispatch).  Every
    slice is kneaded *independently* (its own per-out-channel scales,
    occupancy map, and compacted schedule — slice s's work lists are exactly
    what ``knead_padded(w[s])`` would build) and the resulting arrays stack
    with the leading stack axes: ``planes [*S, B-1, K/32, N]``, ``signs``,
    ``scale``, ``occupancy``, and the schedule's ``counts [*S, NN]`` /
    ``ktile_ids``/``plane_bits [*S, NN, num_work]``.  Scanning this pytree as
    ``xs`` hands the body slice s's :class:`KneadedWeight`, bit-identical to
    the unstacked knead of that slice.

    The work dimension is padded to the *max* ``num_work`` across slices by
    repeating each N-tile's last K-tile with an empty plane mask — the same
    convention as intra-tile ragged padding, so padded grid steps re-request
    resident blocks and run no pass.  A fully-empty slice (an expert pruned
    to all-zero weights) has no last K-tile to repeat and pads with K-tile 0
    instead; its masks are all zero, so every step idles.  Statics on the
    stacked weight: ``num_work`` is the cross-slice max and ``total_work``
    the all-slice sum (a per-slice view therefore
    reports the stack totals — use :meth:`KneadedWeight.work_table` or
    :func:`knead_padded` per slice when per-slice accounting matters).
    """
    if w.ndim < 3:
        raise ValueError(
            f"knead_stacked expects [*stack, K, N] with >=1 stack axis, "
            f"got {w.shape}")
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    per_slice = [knead_padded(flat[s], bits=bits, ks=ks, n_block=n_block)
                 for s in range(flat.shape[0])]
    num_work = max(kw.schedule.num_work for kw in per_slice)

    def pad_ktiles(ids: jax.Array, have: int) -> jax.Array:
        if have == num_work:
            return ids
        if have == 0:   # empty slice: no last K-tile to repeat; counts==0
            return jnp.zeros((ids.shape[0], num_work), ids.dtype)
        return jnp.concatenate(
            [ids, jnp.repeat(ids[:, -1:], num_work - have, axis=1)], axis=1)

    def pad_masks(bits: jax.Array, have: int) -> jax.Array:
        return jnp.pad(bits, ((0, 0), (0, num_work - have)))

    def restack(xs):
        arr = jnp.stack(xs)
        return arr.reshape(lead + arr.shape[1:])

    first = per_slice[0]
    sched = KneadedSchedule(
        counts=restack([kw.schedule.counts for kw in per_slice]),
        ktile_ids=restack([pad_ktiles(kw.schedule.ktile_ids,
                                      kw.schedule.num_work)
                           for kw in per_slice]),
        plane_bits=restack([pad_masks(kw.schedule.plane_bits,
                                      kw.schedule.num_work)
                            for kw in per_slice]),
        num_work=num_work,
        total_work=sum(kw.schedule.total_work for kw in per_slice),
        nk=first.schedule.nk,
        n_tiles=first.schedule.n_tiles,
    )
    return dataclasses.replace(
        first,
        planes=restack([kw.planes for kw in per_slice]),
        signs=restack([kw.signs for kw in per_slice]),
        scale=restack([kw.scale for kw in per_slice]),
        occupancy=restack([kw.occupancy for kw in per_slice]),
        schedule=sched,
    ).with_checksums()     # re-stamp: slice-0 CRCs don't cover the stack


def reknead_like(kw: Union[KneadedWeight, ShardedKneadedWeight],
                 w_float: jax.Array,
                 shards: int = 0) -> Union[KneadedWeight,
                                           ShardedKneadedWeight]:
    """Repair path: rebuild a (possibly corrupted) kneaded weight from its
    float source, with the same knead geometry.

    Kneading is deterministic, so the rebuilt weight is bit-identical to the
    original knead of ``w_float`` — serving that repaired weight produces
    the same outputs as if the corruption never happened (the resilience
    layer's weight-repair guarantee, docs/DESIGN.md §10).  ``shards``
    re-shards stacked/2-D weights when the corrupt weight was sharded
    (pass the engine's shard count; 0/1 = unsharded).  Sharded rebuilds
    keep the original weight's ``partition`` mode — a balanced weight
    repairs to the identical LPT packing (deterministic on identical
    counts), so the repair stays bit-identical.
    """
    stacked = w_float.ndim >= 3
    fresh = (knead_stacked if stacked else knead_padded)(
        w_float, bits=kw.bits, ks=kw.ks, n_block=kw.n_block)
    if shards > 1 or isinstance(kw, ShardedKneadedWeight):
        num = shards if shards > 1 else kw.num_shards
        partition = getattr(kw, "partition", "contiguous")
        fresh = (shard_stacked_schedule if stacked
                 else shard_schedule)(fresh, num, partition=partition)
    return fresh


def kneaded_codes(kw: KneadedWeight) -> jax.Array:
    """Signed integer codes [K, N] reconstructed from the packed planes."""
    mag = bitplanes.unpack_bits(kw.planes, axis=1).astype(jnp.int32)  # [B-1,K,N]
    weights = (2 ** jnp.arange(kw.bits - 1, dtype=jnp.int32)).reshape(-1, 1, 1)
    absq = jnp.sum(mag * weights, axis=0)                             # [K, N]
    sign = 1 - 2 * bitplanes.unpack_bits(kw.signs, axis=0).astype(jnp.int32)
    return absq * sign


def unknead(kw: KneadedWeight) -> jax.Array:
    """Exact float reconstruction: equals dequantize(quantize(w)) of knead()."""
    return kneaded_codes(kw).astype(jnp.float32) * kw.scale
