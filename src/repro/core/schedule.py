"""Occupancy-compacted work schedules for the SAC Pallas kernel.

The occupancy map already knows, at knead time, exactly which
(plane, K-tile, N-tile) blocks carry essential bits.  The dense-grid kernel
still *visited* every block and predicated the dot (``pl.when(occ > 0)``) —
every slack block cost a grid step, an unpack, and a branch.  This module
turns the metadata into a *schedule* instead: per N-tile, the ascending
list of **occupied K-tiles** (those holding at least one non-empty plane
tile), each with a bit mask of its non-empty planes.  The kernel grid walks
one occupied K-tile per step and runs that tile's present planes inside the
step, so executed MXU passes equal the occupancy nonzero count, not
``(B-1) * K/bk * N/bn``, and a wholly empty K-tile costs no grid step.
This is the TPU realization of front-end ineffectual-work scheduling
(Bit-Tactical) + essential-bit-only execution (Laconic): slack is never
dispatched, rather than dispatched-and-skipped.

K-tiles ascend within each N-tile's list, so every plane segment
accumulates its K-tiles in ascending order — the same per-segment
accumulation order as a dense K sweep, which is what keeps the compacted
kernel bit-exact against the planes oracle.  (The order of planes within
one step does not matter: they feed separate accumulators.)

Ragged tiles are padded to the max K-tile count by *repeating the last
occupied K-tile* with an empty plane mask (index maps of padded steps
request already-resident blocks: no DMA; the empty mask runs no pass).
All-empty N-tiles carry count 0 and execute nothing.

**Sharding** (:func:`shard_schedule`, docs/DESIGN.md §5): because the work
lists are independent per N-tile, the schedule partitions along N for free —
each shard of a device mesh takes a contiguous slab of N-tiles together with
exactly those tiles' work lists.  The per-tile lists and their K order
are untouched, so a shard computes its output columns through the *same*
accumulation sequence as the single-device kernel and sharded execution
stays bit-exact.  Load per device is its shard's *occupancy* (the MXU passes
of its tiles), not its dense tile count — the SCNN/Bit-Tactical principle
of distributing the compacted work list rather than the dense iteration
space — and :meth:`ShardedKneadedWeight.imbalance` reports how unevenly the
occupancy landed.

**Balanced sharding** (``partition="balanced"``, docs/DESIGN.md §11):
contiguous slabs inherit whatever skew the occupancy happens to carry — a
column-pruned layer can land all of its work on shard 0 while shard 3 idles.
Because per-tile work is static, the partitioner can do better at shard
time: LPT greedy bin-packing assigns tiles (most passes first) to the
least-loaded shard with free capacity, and the tile→slot permutation is
recorded in ``tile_slot`` so the execution layer can gather the output
columns back into original order.  Per-tile work lists and their K order
are untouched — only *which shard runs which tile* changes — so the
per-tile f32 accumulation sequence, and therefore the output bits, are
identical to the contiguous and unsharded kernels.  All-empty padding tiles
participate in the packing as zero-cost filler, so indivisible tile counts
never inflate ``shard_work`` (contiguous mode pins them to the last shard).

**Stacked sharding** (:func:`shard_stacked_schedule`, docs/DESIGN.md §8):
the LM stacks scan-layer weights as [L, K, N] with per-layer schedules
(``knead_stacked``); sharding applies the same N partition to every layer,
producing a :class:`ShardedStackedKneadedWeight` whose arrays carry
``[L, S, ...]`` axes — layer outermost so ``jax.lax.scan`` slices out each
layer's per-shard slabs, shard axis next for mesh placement.  Per-layer,
per-shard work totals are static (``layer_shard_work``) so load reports
need no device round-trips.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import TYPE_CHECKING, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # avoid the import cycle (kneading imports this module)
    from repro.core.kneading import KneadedWeight

__all__ = ["KneadedIntegrityError", "KneadedSchedule", "ShardedKneadedWeight",
           "ShardedStackedKneadedWeight", "build_schedule", "replay_schedule",
           "shard_schedule", "shard_stacked_schedule", "integrity_checksums",
           "verify_checksums", "tile_passes"]


class KneadedIntegrityError(RuntimeError):
    """A kneaded weight's arrays no longer match their knead-time checksums.

    The kneaded form is an *exact* re-encoding (docs/DESIGN.md §2), which is
    precisely what makes corruption silent and dangerous: a flipped bit in a
    presence word or schedule array changes *which work items the kernel
    executes*, not just an output value.  Serving therefore checksums every
    array at knead time and verifies before trusting restored/transported
    weights (docs/DESIGN.md §10).
    """


def _crc32(x) -> int:
    """CRC32 of an array's raw bytes (host-side; forces a device fetch)."""
    return zlib.crc32(np.ascontiguousarray(np.asarray(x)).tobytes())


def _walk(obj, dotted: str):
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


def integrity_checksums(obj, fields: Tuple[str, ...]
                        ) -> Tuple[Tuple[str, int], ...]:
    """Per-field CRC32s over ``obj``'s (possibly dotted) array fields."""
    return tuple((name, _crc32(_walk(obj, name))) for name in fields)


def verify_checksums(obj, checksums: Tuple[Tuple[str, int], ...]
                     ) -> Tuple[str, ...]:
    """Names of fields whose current bytes mismatch ``checksums``."""
    return tuple(name for name, want in checksums
                 if _crc32(_walk(obj, name)) != want)


def tile_passes(plane_bits) -> np.ndarray:
    """Host-side MXU passes per N-tile: the popcount of every slot's plane
    mask, summed over the slot (last) axis.  Works on any leading stack or
    shard axes."""
    return np.bitwise_count(np.asarray(plane_bits)).astype(np.int64).sum(
        axis=-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KneadedSchedule:
    """Compacted per-N-tile K-tile walks for one kneaded weight.

    Attributes:
      counts:     int32 [N/n_block] — occupied K-tiles per N-tile (the grid
                  steps that tile runs).
      ktile_ids:  int32 [N/n_block, num_work] — the occupied K-tiles,
                  ascending.  Entries past ``counts[j]`` repeat the tile's
                  last occupied K-tile (or 0 for all-empty tiles) so padded
                  grid steps re-request resident blocks.
      plane_bits: int32 [N/n_block, num_work] — per slot, bit ``b`` set iff
                  plane ``b`` of that K-tile is non-empty; 0 past
                  ``counts[j]``.  This is the kernel's static plane mask.
      num_work:   static grid extent of the work dimension:
                  ``max(1, max(counts))`` (>= 1 so init/epilogue always run).
      total_work: static popcount of ``plane_bits`` == occupancy nonzero
                  count == MXU passes the kernel executes per M-step row of
                  the grid.
      nk, n_tiles: static dense extents (K/ks, N/n_block) — the dense
                  schedule would be ``(B-1) * nk`` passes per N-tile.
    """

    counts: jax.Array
    ktile_ids: jax.Array
    plane_bits: jax.Array
    num_work: int = dataclasses.field(metadata=dict(static=True), default=1)
    total_work: int = dataclasses.field(metadata=dict(static=True), default=0)
    nk: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_tiles: int = dataclasses.field(metadata=dict(static=True), default=0)

    def dense_work(self, bits: int) -> int:
        """MXU passes the dense grid would execute: (B-1) * K/ks * N/n_block."""
        return (bits - 1) * self.nk * self.n_tiles

    @property
    def planes_per_step(self) -> float:
        """MXU passes per executed grid step, ``total_work / sum(counts)``
        (host-side): B-1 where every occupied K-tile has all its planes,
        falling toward 1 as the weight-side skip empties planes; 0.0 for a
        schedule with no work."""
        steps = int(np.asarray(self.counts).sum())
        return self.total_work / steps if steps else 0.0

    def metadata_bytes(self) -> int:
        return (self.counts.size + self.ktile_ids.size
                + self.plane_bits.size) * 4


def build_schedule(occupancy_map: jax.Array) -> KneadedSchedule:
    """Flatten an occupancy presence map into a compacted schedule.

    Args:
      occupancy_map: {0,1} int array [B-1, K/ks, N/n_block] (the unpacked
        pass-mark metadata).  Host-side (numpy) — kneading is an offline
        conversion, and ``num_work``/``total_work`` must be static.
    Returns:
      A :class:`KneadedSchedule` whose slots enumerate exactly the occupied
      K-tiles per N-tile, ascending, each with its non-empty plane mask.
    """
    occ = np.asarray(occupancy_map) != 0                   # [B-1, NK, NN]
    nb, nk, nn = occ.shape
    weights = (1 << np.arange(nb, dtype=np.int64)).reshape(-1, 1, 1)
    masks = (occ * weights).sum(axis=0).astype(np.int32)   # [NK, NN]
    counts = (masks != 0).sum(axis=0).astype(np.int32)     # [NN]
    num_work = max(1, int(counts.max(initial=0)))
    ktile_ids = np.zeros((nn, num_work), np.int32)
    plane_bits = np.zeros((nn, num_work), np.int32)
    for j in range(nn):
        kt = np.flatnonzero(masks[:, j])                   # ascending
        c = kt.size
        if c:
            ktile_ids[j, :c], ktile_ids[j, c:] = kt, kt[-1]
            plane_bits[j, :c] = masks[kt, j]
    return KneadedSchedule(
        counts=jnp.asarray(counts),
        ktile_ids=jnp.asarray(ktile_ids),
        plane_bits=jnp.asarray(plane_bits),
        num_work=num_work,
        total_work=int(occ.sum()),
        nk=nk,
        n_tiles=nn,
    )


def replay_schedule(a, kw) -> jax.Array:
    """Executable spec of the compacted kernel: walk the schedule on the host.

    Replays, in numpy, exactly the passes the kernel's grid executes — per
    N-tile, per occupied K-tile slot ``w < counts[j]``, per plane in that
    slot's mask, one f32 dot accumulated into the plane's segment, then the
    rear-adder-tree epilogue.  Used by the schedule property tests as the
    order-faithful oracle; bit-exact against both ``impl="planes"`` and
    ``impl="pallas"``.

    Control flow (which passes run, in what order) is host-side numpy over
    the schedule arrays; the arithmetic itself is the same jnp ops as the
    planes oracle, so accumulation rounding is identical
    operation-for-operation.

    Args:
      a:  [M, K] activations (K == kw.k, stored/padded dim).
      kw: a :class:`repro.core.kneading.KneadedWeight` with a schedule.
    """
    from repro.core import bitplanes

    sched = kw.schedule
    mag = bitplanes.unpack_bits(kw.planes, axis=1)               # [B-1, K, N]
    sign = 1 - 2 * bitplanes.unpack_bits(kw.signs, axis=0).astype(jnp.int8)
    a32 = jnp.asarray(a, jnp.float32)
    counts = np.asarray(sched.counts)
    ktile_ids = np.asarray(sched.ktile_ids)
    plane_bits = np.asarray(sched.plane_bits)
    ks, nb = kw.ks, kw.n_block
    m = a32.shape[0]
    weights = (2.0 ** jnp.arange(kw.bits - 1)).reshape(-1, 1, 1)
    out_tiles = []
    for j in range(sched.n_tiles):
        nsl = slice(j * nb, (j + 1) * nb)
        seg = [jnp.zeros((m, nb), jnp.float32) for _ in range(kw.bits - 1)]
        for w in range(int(counts[j])):                # occupied K-tiles only
            t, bits_w = int(ktile_ids[j, w]), int(plane_bits[j, w])
            ksl = slice(t * ks, (t + 1) * ks)
            for b in range(kw.bits - 1):
                if not (bits_w >> b) & 1:
                    continue                           # empty plane tile
                plane = (mag[b, ksl, nsl].astype(jnp.int8)
                         * sign[ksl, nsl]).astype(jnp.float32)
                seg[b] = seg[b] + a32[:, ksl] @ plane  # S_b += A_t @ P_bt
        out_tiles.append(jnp.sum(jnp.stack(seg) * weights, axis=0))
    out = jnp.concatenate(out_tiles, axis=1)
    return out * kw.scale


# ---------------------------------------------------------------------------
# N-sharded schedules (docs/DESIGN.md §5)
# ---------------------------------------------------------------------------

PARTITIONS = ("contiguous", "balanced")


def _lpt_tile_slots(counts: np.ndarray, num_shards: int,
                    tiles_per_shard: int) -> np.ndarray:
    """LPT bin-packing of N-tiles onto shards with per-shard tile capacity.

    Longest-Processing-Time greedy: visit tiles by occupancy count
    descending (stable order, so equal counts keep their tile-index order)
    and place each on the least-loaded shard that still has a free tile
    slot, lowest shard index on ties.  Padding tiles (count 0) participate
    like any other tile — they fill leftover capacity and add no load.
    Deterministic: same counts => same packing, which the integrity
    checksums and the repair path rely on.

    Returns int32 ``slot`` with ``slot[j] = s * tiles_per_shard + p``: tile
    ``j`` lands in position ``p`` of shard ``s``.  ``slot`` is a bijection
    on ``range(num_shards * tiles_per_shard)`` — every tile is placed
    exactly once, every slot filled exactly once.
    """
    counts = np.asarray(counts, dtype=np.int64)
    total = num_shards * tiles_per_shard
    if counts.shape != (total,):
        raise ValueError(f"expected {total} padded tiles, got {counts.shape}")
    order = np.argsort(-counts, kind="stable")     # heaviest first
    load = np.zeros(num_shards, dtype=np.int64)
    fill = np.zeros(num_shards, dtype=np.int64)
    slot = np.empty(total, dtype=np.int32)
    for j in order:
        open_shards = np.flatnonzero(fill < tiles_per_shard)
        s = open_shards[np.argmin(load[open_shards])]  # argmin: lowest index
        slot[j] = s * tiles_per_shard + fill[s]
        load[s] += counts[j]
        fill[s] += 1
    return slot


def _balanced_tile_slots(counts: np.ndarray, num_shards: int,
                         tiles_per_shard: int) -> np.ndarray:
    """Tile→slot assignment for ``partition="balanced"``.

    LPT packing (:func:`_lpt_tile_slots`), falling back to the contiguous
    identity when that packing's max shard load is *worse*: LPT is a
    4/3-approximation, so a contiguous layout that happens to be optimal
    can beat the greedy (e.g. counts ``[3,3,0,2,2,2]`` at 2 shards pack
    greedily to max 7 while the contiguous slabs hit the optimal 6).
    Taking the better of the two makes balanced mode never worse than
    contiguous — the monotonicity the property suite pins.
    """
    slot = _lpt_tile_slots(counts, num_shards, tiles_per_shard)
    counts = np.asarray(counts, dtype=np.int64)
    lpt_max = np.bincount(slot // tiles_per_shard, weights=counts,
                          minlength=num_shards).max()
    cont_max = counts.reshape(num_shards, tiles_per_shard).sum(axis=1).max()
    if lpt_max <= cont_max:
        return slot
    return np.arange(counts.size, dtype=np.int32)


def _check_partition(partition: str) -> None:
    if partition not in PARTITIONS:
        raise ValueError(f"partition must be one of {PARTITIONS}, "
                         f"got {partition!r}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedKneadedWeight:
    """A kneaded weight partitioned along N into per-device work-list shards.

    Every array carries a leading shard axis of extent ``num_shards``; placed
    with :func:`repro.runtime.sharding.kneaded_shardings`, that axis maps one
    slab per mesh device, and ``jax.shard_map`` hands each device its own
    planes/signs/scale slab *plus its own compacted schedule* — the device
    executes only the occupancy nonzeros of its N-tiles, never the dense
    tile count.

    Attributes:
      planes:    uint32 [S, B-1, K/32, n/S] — magnitude planes, N-sliced.
      signs:     uint32 [S, K/32, n/S].
      scale:     f32   [S, 1, n/S].
      counts:    int32 [S, T] per-shard occupied K-tile counts
                 (T = tiles_per_shard).
      ktile_ids / plane_bits: int32 [S, T, num_work] per-shard K-tile walks
                 and their plane masks.
      tile_slot: int32 [S*T] — the tile→slot permutation: original N-tile
                 ``j`` lives in flattened packed slot ``tile_slot[j]``
                 (shard ``tile_slot[j] // T``, position ``% T``).  Identity
                 for ``partition="contiguous"``; for "balanced" it is both
                 the packing record and, directly, the gather index the
                 execution layer uses to restore original column order.
      num_shards, num_work, nk, tiles_per_shard: static grid extents; the
                 work dim is padded to the *global* max so every shard runs
                 the same program under shard_map.
      partition: static partitioning mode ("contiguous" | "balanced").
      shard_work: static per-shard occupancy-nonzero totals (the load each
                 device actually executes per M-step; see :meth:`imbalance`).
      bits, ks, n_block, k, n, k_orig, n_orig: as on ``KneadedWeight``; ``n``
                 is the sharded stored extent (tile padding may grow it when
                 N-tiles don't divide ``num_shards`` — padded tiles carry
                 count 0 and cost no MXU passes).
    """

    planes: jax.Array
    signs: jax.Array
    scale: jax.Array
    counts: jax.Array
    ktile_ids: jax.Array
    plane_bits: jax.Array
    tile_slot: jax.Array
    num_shards: int = dataclasses.field(metadata=dict(static=True), default=1)
    num_work: int = dataclasses.field(metadata=dict(static=True), default=1)
    nk: int = dataclasses.field(metadata=dict(static=True), default=0)
    tiles_per_shard: int = dataclasses.field(metadata=dict(static=True),
                                             default=0)
    shard_work: Tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True), default=())
    bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    ks: int = dataclasses.field(metadata=dict(static=True), default=256)
    n_block: int = dataclasses.field(metadata=dict(static=True), default=128)
    k: int = dataclasses.field(metadata=dict(static=True), default=0)
    n: int = dataclasses.field(metadata=dict(static=True), default=0)
    k_orig: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_orig: int = dataclasses.field(metadata=dict(static=True), default=0)
    partition: str = dataclasses.field(metadata=dict(static=True),
                                       default="contiguous")
    # knead/shard-time per-field CRC32s ((field, crc) pairs; () = unchecked)
    checksums: Tuple[Tuple[str, int], ...] = dataclasses.field(
        metadata=dict(static=True), default=())

    _INTEGRITY_FIELDS = ("planes", "signs", "scale", "counts",
                         "ktile_ids", "plane_bits", "tile_slot")

    def with_checksums(self) -> "ShardedKneadedWeight":
        """Stamp shard-time CRC32s over every array field (host-side)."""
        return dataclasses.replace(
            self, checksums=integrity_checksums(self, self._INTEGRITY_FIELDS))

    def verify(self, strict: bool = False) -> Tuple[str, ...]:
        """Names of array fields whose bytes changed since sharding
        (empty tuple = intact, or no checksums recorded).  ``strict``
        raises :class:`KneadedIntegrityError` instead."""
        bad = verify_checksums(self, self.checksums)
        if bad and strict:
            raise KneadedIntegrityError(
                f"sharded kneaded weight [{self.k}x{self.n} "
                f"s={self.num_shards}] corrupt in: {', '.join(bad)}")
        return bad

    @property
    def shard_n(self) -> int:
        """Stored output columns held by each shard."""
        return self.n // self.num_shards

    @property
    def logical_k(self) -> int:
        return self.k_orig or self.k

    @property
    def logical_n(self) -> int:
        return self.n_orig or self.n

    @property
    def total_work(self) -> int:
        """Occupancy nonzeros across all shards == unsharded total_work."""
        return sum(self.shard_work)

    @property
    def _orig_n_tiles(self) -> int:
        """N-tiles of the weight before shard padding (== the unsharded
        schedule's n_tiles; knead already padded logical_n to n_block)."""
        return -(-self.logical_n // self.n_block)

    def dense_work(self) -> int:
        """MXU passes the dense grid would execute across all shards.

        Counts the pre-shard-padding tiles only, so this equals the
        unsharded ``KneadedSchedule.dense_work`` — all-empty shard-padding
        tiles must not inflate the denominator of skip ratios.
        """
        return (self.bits - 1) * self.nk * self._orig_n_tiles

    def schedule_for(self, s: int) -> KneadedSchedule:
        """Shard ``s``'s compacted schedule (the program each device runs)."""
        return KneadedSchedule(
            counts=self.counts[s],
            ktile_ids=self.ktile_ids[s],
            plane_bits=self.plane_bits[s],
            num_work=self.num_work,
            total_work=self.shard_work[s],
            nk=self.nk,
            n_tiles=self.tiles_per_shard,
        )

    def imbalance(self) -> dict:
        """Per-shard load report: executed work per device and skew.

        ``imbalance`` is max/mean shard work (1.0 == perfectly balanced); a
        shard with zero work contributes 0 to the mean but still holds a
        device, so heavily skewed occupancy shows up directly here.
        """
        work = list(self.shard_work)
        mean = sum(work) / max(1, len(work))
        return {
            "shard_work": work,
            "max": max(work) if work else 0,
            "mean": mean,
            "imbalance": (max(work) / mean) if mean else 1.0,
        }

    def metadata_bytes(self) -> int:
        return (self.counts.size + self.ktile_ids.size
                + self.plane_bits.size + self.tile_slot.size) * 4

    def packed_bytes(self) -> int:
        """HBM bytes across all shards: planes + signs + scales + schedule."""
        return (self.planes.size * 4 + self.signs.size * 4
                + self.scale.size * 4 + self.metadata_bytes())

    def dense_bf16_bytes(self) -> int:
        """bf16 bytes of the pre-shard-padding stored weight — same
        denominator as the unsharded report, so bytes_vs_bf16 keeps its
        meaning regardless of shard count."""
        return self.k * self._orig_n_tiles * self.n_block * 2


def _mesh_axis_size(mesh, axis: str) -> int:
    if isinstance(mesh, int):
        return mesh
    return mesh.shape[axis]


def shard_schedule(kw: "KneadedWeight",
                   mesh: Union[int, jax.sharding.Mesh],
                   axis: str = "model",
                   partition: str = "contiguous") -> ShardedKneadedWeight:
    """Partition a kneaded weight + its schedule along N for a device mesh.

    ``partition="contiguous"`` (default): each of the ``mesh.shape[axis]``
    shards receives a contiguous slab of N-tiles with exactly those tiles'
    compacted work lists — per-tile K-tile walks unchanged, so sharded
    outputs are bit-exact against the single-device kernel.

    ``partition="balanced"``: tiles are LPT bin-packed onto shards by their
    static MXU passes (:func:`_lpt_tile_slots`), so
    ``max(shard_work)`` approaches ``ceil(total_work / S)`` regardless of
    where the occupancy landed.  The tile→slot permutation is recorded in
    ``tile_slot``; the execution layer gathers output N-blocks back into
    original order (``sac_matmul_pallas_sharded``), and because per-tile
    work lists and their K order are untouched, the gathered output is
    still bit-exact against the single-device kernel (docs/DESIGN.md §11).

    When the N-tile count does not divide the shard count, all-empty
    padding tiles (count 0, zero weight columns, scale 1.0) are appended so
    every shard holds ``tiles_per_shard`` tiles; like knead padding, they
    cost metadata only, never an MXU pass, and the padded output columns
    sit past ``logical_n`` where callers already slice.  Under "balanced"
    the padding tiles join the packing as zero-cost filler instead of
    piling onto the last shard.

    Args:
      kw:   a :class:`repro.core.kneading.KneadedWeight`.
      mesh: the target mesh (or a plain int shard count for host-side
            analysis, e.g. the benchmark imbalance sweeps).
      axis: mesh axis name to shard over (the serving meshes call it
            "model" — out-channel partitioning is tensor parallelism).
      partition: "contiguous" | "balanced".
    Returns:
      A :class:`ShardedKneadedWeight` with one leading shard axis on every
      array, ready for ``runtime.sharding.kneaded_shardings`` placement.
    """
    _check_partition(partition)
    sched = kw.schedule
    num = _mesh_axis_size(mesh, axis)
    if num < 1:
        raise ValueError(f"shard count must be >= 1, got {num}")
    nn = sched.n_tiles
    tps = -(-nn // num)                       # tiles per shard (ceil)
    pad_tiles = tps * num - nn
    pad_cols = pad_tiles * kw.n_block
    n_pad = kw.n + pad_cols
    total = num * tps

    planes, signs = kw.planes, kw.signs
    scale = jnp.broadcast_to(jnp.asarray(kw.scale, jnp.float32)
                             .reshape(1, -1), (1, kw.n))
    counts = sched.counts
    ktile_ids, plane_bits = sched.ktile_ids, sched.plane_bits
    if pad_tiles:
        planes = jnp.pad(planes, ((0, 0), (0, 0), (0, pad_cols)))
        signs = jnp.pad(signs, ((0, 0), (0, pad_cols)))
        scale = jnp.pad(scale, ((0, 0), (0, pad_cols)), constant_values=1.0)
        counts = jnp.pad(counts, (0, pad_tiles))
        ktile_ids = jnp.pad(ktile_ids, ((0, pad_tiles), (0, 0)))
        plane_bits = jnp.pad(plane_bits, ((0, pad_tiles), (0, 0)))

    host_passes = tile_passes(plane_bits)
    if partition == "balanced":
        slot = _balanced_tile_slots(host_passes, num, tps)
        inv = np.argsort(slot).astype(np.int32)  # inv[s] = tile in slot s
        inv_j = jnp.asarray(inv)
        nb_ = kw.bits - 1
        kwords_ = kw.k // 32
        planes = jnp.take(planes.reshape(nb_, kwords_, total, kw.n_block),
                          inv_j, axis=2).reshape(nb_, kwords_, n_pad)
        signs = jnp.take(signs.reshape(kwords_, total, kw.n_block),
                         inv_j, axis=1).reshape(kwords_, n_pad)
        scale = jnp.take(scale.reshape(1, total, kw.n_block),
                         inv_j, axis=1).reshape(1, n_pad)
        counts = jnp.take(counts, inv_j, axis=0)
        ktile_ids = jnp.take(ktile_ids, inv_j, axis=0)
        plane_bits = jnp.take(plane_bits, inv_j, axis=0)
        host_passes = host_passes[inv]
    else:
        slot = np.arange(total, dtype=np.int32)

    shard_n = n_pad // num
    nb = kw.bits - 1
    kwords = kw.k // 32
    shard_work = tuple(
        int(c) for c in host_passes.reshape(num, tps).sum(axis=1))
    return ShardedKneadedWeight(
        planes=planes.reshape(nb, kwords, num, shard_n).transpose(2, 0, 1, 3),
        signs=signs.reshape(kwords, num, shard_n).transpose(1, 0, 2),
        scale=scale.reshape(1, num, shard_n).transpose(1, 0, 2),
        counts=counts.reshape(num, tps),
        ktile_ids=ktile_ids.reshape(num, tps, sched.num_work),
        plane_bits=plane_bits.reshape(num, tps, sched.num_work),
        tile_slot=jnp.asarray(slot),
        num_shards=num,
        num_work=sched.num_work,
        nk=sched.nk,
        tiles_per_shard=tps,
        shard_work=shard_work,
        bits=kw.bits, ks=kw.ks, n_block=kw.n_block,
        k=kw.k, n=n_pad,
        k_orig=kw.k_orig, n_orig=kw.n_orig or (kw.n if pad_tiles else 0),
        partition=partition,
    ).with_checksums()


# ---------------------------------------------------------------------------
# Stacked (scan-layer) N-sharded schedules (docs/DESIGN.md §8)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedStackedKneadedWeight(ShardedKneadedWeight):
    """A stacked [L, K, N] kneaded weight sharded along N, per layer.

    Every array field of :class:`ShardedKneadedWeight` gains a leading
    *layer* axis in front of the shard axis: ``planes [L, S, B-1, K/32,
    n/S]``, ``counts [L, S, T]``, work lists ``[L, S, T, num_work]``, and so
    on.  The layer axis stays outermost because ``jax.lax.scan`` slices
    leading axes only — scanning this pytree as ``xs`` hands the body layer
    *l*'s arrays with their leading shard axis intact, i.e. exactly the
    per-layer sharded weight ``shard_schedule(knead_padded(w[l]))`` would
    build (up to the work dim, padded to the cross-layer/cross-shard max so
    every layer and every shard runs the same kernel program).  The shard
    axis (axis 1 here; axis 0 after the scan slice) is the one
    ``runtime.sharding`` places on the mesh.

    Statics: ``num_layers`` is the stack extent; ``layer_shard_work[l][s]``
    the occupancy-nonzero count layer *l* dispatches on shard *s* (each row
    partitions that layer's unsharded ``total_work``); the inherited
    ``shard_work[s]`` aggregates over layers — the per-device load of one
    full forward pass through the stack.
    """

    num_layers: int = dataclasses.field(metadata=dict(static=True), default=0)
    layer_shard_work: Tuple[Tuple[int, ...], ...] = dataclasses.field(
        metadata=dict(static=True), default=())

    def layer_schedule_for(self, layer: int, s: int) -> KneadedSchedule:
        """Layer ``layer``'s compacted schedule on shard ``s`` (host-side
        full object; a scan-sliced per-layer object uses the inherited
        :meth:`ShardedKneadedWeight.schedule_for` instead)."""
        return KneadedSchedule(
            counts=self.counts[layer, s],
            ktile_ids=self.ktile_ids[layer, s],
            plane_bits=self.plane_bits[layer, s],
            num_work=self.num_work,
            total_work=self.layer_shard_work[layer][s],
            nk=self.nk,
            n_tiles=self.tiles_per_shard,
        )

    def dense_work(self) -> int:
        """Dense-grid MXU passes across all layers and shards (stack-level
        accounting, matching the stacked ``total_work`` convention)."""
        return self.num_layers * super().dense_work()

    def dense_bf16_bytes(self) -> int:
        return self.num_layers * super().dense_bf16_bytes()

    def layer_imbalance(self, layer: int) -> dict:
        """Per-shard load report for one layer (same keys as
        :meth:`ShardedKneadedWeight.imbalance`)."""
        work = list(self.layer_shard_work[layer])
        mean = sum(work) / max(1, len(work))
        return {
            "shard_work": work,
            "max": max(work) if work else 0,
            "mean": mean,
            "imbalance": (max(work) / mean) if mean else 1.0,
        }

    def imbalance(self) -> dict:
        """Aggregate per-shard load over the whole stack, plus the worst
        single layer's skew (a layer whose occupancy lands on one shard
        serializes that layer even if the stack totals balance)."""
        rep = super().imbalance()
        if self.layer_shard_work:
            rep["max_layer_imbalance"] = max(
                self.layer_imbalance(layer)["imbalance"]
                for layer in range(self.num_layers))
        return rep


def shard_stacked_schedule(kw: "KneadedWeight",
                           mesh: Union[int, jax.sharding.Mesh],
                           axis: str = "model",
                           partition: str = "contiguous",
                           ) -> ShardedStackedKneadedWeight:
    """Partition a stacked [L, K, N] kneaded weight along N for a mesh.

    ``kw`` is a stacked weight from :func:`repro.core.kneading.knead_stacked`
    (leading layer axis on every array, schedule ``counts [L, NN]`` / work
    lists ``[L, NN, num_work]``).  Every layer's per-N-tile work lists are
    partitioned exactly as :func:`shard_schedule` partitions one layer's —
    shard *s* of layer *l* takes the same contiguous slab of N-tiles with
    those tiles' K-tile walks, order untouched, so the sharded
    stack is bit-exact against the unsharded one layer by layer.  All layers
    share the (already cross-layer-padded) ``num_work``, so the whole stack
    runs one kernel program.

    ``partition="balanced"`` repartitions **per layer**: each layer's tiles
    are LPT-packed on that layer's own passes (occupancy skew is per-layer
    — one layer's hot tiles are another's empty ones), giving ``tile_slot``
    a leading layer axis ``[L, S*T]`` that ``jax.lax.scan`` slices together
    with the weight arrays, so the per-layer gather in the execution layer
    sees exactly its layer's permutation.  The shared cross-layer
    ``num_work`` pad is untouched — every layer still runs one kernel
    program, only its tile→shard placement differs.

    Indivisible N-tile counts append all-empty padding tiles per layer (the
    same tiles on every layer — the stack shares [K, N]); padded columns sit
    past ``logical_n`` where callers already slice.

    Args:
      kw:   a *stacked* :class:`repro.core.kneading.KneadedWeight`.
      mesh: target mesh or plain int shard count (host-side analysis).
      axis: mesh axis name for the shard dimension.
      partition: "contiguous" | "balanced" (per-layer LPT).
    Returns:
      A :class:`ShardedStackedKneadedWeight` with axes ``[L, S, ...]`` —
      scan-sliceable per layer, shard axis placed by
      ``runtime.sharding.kneaded_shardings``.
    """
    _check_partition(partition)
    sched = kw.schedule
    if kw.planes.ndim != 4:
        raise ValueError("shard_stacked_schedule expects a stacked kneaded "
                         f"weight (planes [L, B-1, K/32, N]), got planes "
                         f"shape {tuple(kw.planes.shape)}")
    num = _mesh_axis_size(mesh, axis)
    if num < 1:
        raise ValueError(f"shard count must be >= 1, got {num}")
    layers = kw.planes.shape[0]
    nn = sched.n_tiles
    tps = -(-nn // num)                       # tiles per shard (ceil)
    pad_tiles = tps * num - nn
    pad_cols = pad_tiles * kw.n_block
    n_pad = kw.n + pad_cols
    total = num * tps

    planes, signs = kw.planes, kw.signs                  # [L, B-1, K/32, N]
    scale = jnp.broadcast_to(
        jnp.asarray(kw.scale, jnp.float32).reshape(layers, 1, -1),
        (layers, 1, kw.n))
    counts = sched.counts                                 # [L, NN]
    ktile_ids, plane_bits = sched.ktile_ids, sched.plane_bits
    if pad_tiles:
        planes = jnp.pad(planes, ((0, 0),) * 3 + ((0, pad_cols),))
        signs = jnp.pad(signs, ((0, 0),) * 2 + ((0, pad_cols),))
        scale = jnp.pad(scale, ((0, 0), (0, 0), (0, pad_cols)),
                        constant_values=1.0)
        counts = jnp.pad(counts, ((0, 0), (0, pad_tiles)))
        ktile_ids = jnp.pad(ktile_ids, ((0, 0), (0, pad_tiles), (0, 0)))
        plane_bits = jnp.pad(plane_bits, ((0, 0), (0, pad_tiles), (0, 0)))

    host_passes = tile_passes(plane_bits)                 # [L, total]
    if partition == "balanced":
        slot = np.stack([_balanced_tile_slots(host_passes[layer], num, tps)
                         for layer in range(layers)])     # [L, total]
        inv = np.argsort(slot, axis=1).astype(np.int32)
        inv_j = jnp.asarray(inv)
        nb_ = kw.bits - 1
        kwords_ = kw.k // 32
        planes = jnp.take_along_axis(
            planes.reshape(layers, nb_, kwords_, total, kw.n_block),
            inv_j[:, None, None, :, None], axis=3,
        ).reshape(layers, nb_, kwords_, n_pad)
        signs = jnp.take_along_axis(
            signs.reshape(layers, kwords_, total, kw.n_block),
            inv_j[:, None, :, None], axis=2,
        ).reshape(layers, kwords_, n_pad)
        scale = jnp.take_along_axis(
            scale.reshape(layers, 1, total, kw.n_block),
            inv_j[:, None, :, None], axis=2,
        ).reshape(layers, 1, n_pad)
        counts = jnp.take_along_axis(counts, inv_j, axis=1)
        ktile_ids = jnp.take_along_axis(ktile_ids, inv_j[:, :, None], axis=1)
        plane_bits = jnp.take_along_axis(plane_bits, inv_j[:, :, None],
                                         axis=1)
        host_passes = np.take_along_axis(host_passes, inv, axis=1)
    else:
        slot = np.broadcast_to(np.arange(total, dtype=np.int32),
                               (layers, total)).copy()

    shard_n = n_pad // num
    nb = kw.bits - 1
    kwords = kw.k // 32
    per_layer_work = host_passes.reshape(layers, num, tps).sum(axis=2)
    layer_shard_work = tuple(tuple(int(c) for c in row)
                             for row in per_layer_work)
    shard_work = tuple(int(c) for c in per_layer_work.sum(axis=0))
    return ShardedStackedKneadedWeight(
        planes=planes.reshape(layers, nb, kwords, num, shard_n)
                     .transpose(0, 3, 1, 2, 4),
        signs=signs.reshape(layers, kwords, num, shard_n)
                   .transpose(0, 2, 1, 3),
        scale=scale.reshape(layers, 1, num, shard_n).transpose(0, 2, 1, 3),
        counts=counts.reshape(layers, num, tps),
        ktile_ids=ktile_ids.reshape(layers, num, tps, sched.num_work),
        plane_bits=plane_bits.reshape(layers, num, tps, sched.num_work),
        tile_slot=jnp.asarray(slot),
        num_shards=num,
        num_work=sched.num_work,
        nk=sched.nk,
        tiles_per_shard=tps,
        shard_work=shard_work,
        bits=kw.bits, ks=kw.ks, n_block=kw.n_block,
        k=kw.k, n=n_pad,
        k_orig=kw.k_orig, n_orig=kw.n_orig or (kw.n if pad_tiles else 0),
        partition=partition,
        num_layers=layers,
        layer_shard_work=layer_shard_work,
    ).with_checksums()
