"""Compacted-schedule correctness: structure, parity, and extremes.

The KneadedSchedule is *the* execution plan of the Pallas kernel — these
tests pin (a) its structural invariants against the occupancy map it was
built from, (b) bit-exact output parity of the schedule-driven kernel vs the
dense planes oracle vs the pass-by-pass ``replay_schedule`` spec across
random shapes and sparsities, (c) the all-empty / all-dense occupancy
extremes the grid must survive (num_work floor of 1; zero dispatched work),
and (d) the balanced shard partitioner's invariants (docs/DESIGN.md §11):
for any occupancy, ``partition="balanced"`` never loads its worst shard
more than contiguous does, its ``tile_slot`` is a bijection covering every
N-tile, and the permuted-then-gathered execution stays bit-exact against
the unsharded kernel across the sparsity extremes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import knead, sac_matmul
from repro.core.bitplanes import pack_presence, unpack_presence
from repro.core.kneading import knead_padded, knead_stacked
from repro.core.schedule import (build_schedule, replay_schedule,
                                 shard_schedule, shard_stacked_schedule)
from repro.kernels.sac_matmul.ops import (sac_matmul_pallas,
                                          sac_matmul_pallas_sharded)

settings.register_profile("ci2", deadline=None, max_examples=15)
settings.load_profile("ci2")


def _sparse_w(seed, k, n, sparsity):
    kk = jax.random.split(jax.random.PRNGKey(seed), 2)
    w = jax.random.normal(kk[0], (k, n)) * 0.05
    if sparsity > 0:
        keep = jax.random.uniform(kk[1], (k, n)) >= sparsity
        w = w * keep
    return w


# ----------------------------------------------------------- structure
def test_schedule_structure_matches_occupancy():
    """Schedule slots enumerate exactly the occupied K-tiles per N-tile,
    ascending, each with the mask of its nonzero planes, padded by
    repeating the last K-tile with an empty mask."""
    rng = np.random.default_rng(0)
    occ = (rng.random((7, 5, 3)) < 0.3).astype(np.int32)
    sched = build_schedule(occ)
    assert sched.total_work == int(occ.sum())
    assert sched.nk == 5 and sched.n_tiles == 3
    occupied = occ.any(axis=0)                            # [NK, NN]
    assert sched.num_work == max(1, int(occupied.sum(axis=0).max()))
    counts = np.asarray(sched.counts)
    pbits, kid = np.asarray(sched.plane_bits), np.asarray(sched.ktile_ids)
    assert int(np.bitwise_count(pbits).sum()) == sched.total_work
    for j in range(3):
        c = int(counts[j])
        assert c == int(occupied[:, j].sum())
        # exactly the occupied K-tiles, ascending
        assert kid[j, :c].tolist() == np.flatnonzero(occupied[:, j]).tolist()
        for w in range(c):       # each slot's mask names its nonzero planes
            planes = [b for b in range(7) if (pbits[j, w] >> b) & 1]
            assert planes == np.flatnonzero(occ[:, kid[j, w], j]).tolist()
        assert (pbits[j, c:] == 0).all()       # padding runs no plane
        if c:  # padding repeats the last K-tile (no new blocks fetched)
            assert (kid[j, c:] == kid[j, c - 1]).all()
        else:
            assert (kid[j] == 0).all()


def test_pack_presence_roundtrip():
    rng = np.random.default_rng(1)
    occ = (rng.random((7, 37, 4)) < 0.5).astype(np.int32)   # NK not | 32
    packed = pack_presence(jnp.asarray(occ))
    assert packed.dtype == jnp.uint32 and packed.shape == (7, 2, 4)
    assert np.array_equal(np.asarray(unpack_presence(packed, 37)), occ)


# ------------------------------------------------- parity (property-based)
@given(seed=st.integers(0, 10),
       shape=st.sampled_from([(8, 256, 128), (8, 512, 128), (4, 512, 256)]),
       bits=st.sampled_from([4, 8]),
       sparsity=st.sampled_from([0.0, 0.7, 0.95]))
def test_schedule_parity_bit_exact(seed, shape, bits, sparsity):
    """Compacted kernel == dense planes oracle == schedule replay, bitwise,
    across shapes and occupancy densities."""
    m, k, n = shape
    w = _sparse_w(seed, k, n, sparsity)
    a = jax.random.normal(jax.random.PRNGKey(seed + 99), (m, k))
    kw = knead(w, bits=bits, ks=256, n_block=128)
    out_planes = sac_matmul(a, kw, impl="planes")
    out_pallas = sac_matmul(a, kw, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out_pallas),
                                  np.asarray(out_planes))
    out_replay = replay_schedule(a, kw)[:, :kw.logical_n]
    np.testing.assert_array_equal(np.asarray(out_replay),
                                  np.asarray(out_planes))


def test_schedule_parity_sparse_smoke():
    """Non-hypothesis fallback of the parity property: one sparse case runs
    in every environment (the @given sweep broadens it when hypothesis is
    installed)."""
    # element sparsity alone rarely empties a whole 256x128 tile — zero the
    # second K block outright so the schedule provably compacts
    w = _sparse_w(5, 512, 128, sparsity=0.9).at[256:].set(0.0)
    a = jax.random.normal(jax.random.PRNGKey(6), (8, 512))
    kw = knead(w, bits=8, ks=256, n_block=128)
    assert kw.schedule.total_work < kw.schedule.dense_work(kw.bits)
    out_planes = sac_matmul(a, kw, impl="planes")
    out_pallas = sac_matmul(a, kw, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out_pallas),
                                  np.asarray(out_planes))
    out_replay = replay_schedule(a, kw)[:, :kw.logical_n]
    np.testing.assert_array_equal(np.asarray(out_replay),
                                  np.asarray(out_planes))


# --------------------------------------------------------------- extremes
def test_schedule_all_empty():
    """An all-zero weight schedules ZERO work; the kernel must still write
    its (all-zero) output through the num_work >= 1 grid floor."""
    w = jnp.zeros((512, 128))
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 512))
    kw = knead(w, bits=8, ks=256, n_block=128)
    assert kw.schedule.total_work == 0
    assert kw.schedule.num_work == 1            # grid floor, idles through
    assert int(np.asarray(kw.schedule.counts).sum()) == 0
    out = sac_matmul_pallas(a, kw, bm=8)
    assert out.shape == (8, 128)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((8, 128), np.float32))


def test_schedule_all_dense():
    """Fully-occupied weights schedule the dense work count — compaction
    never *adds* work, and parity still holds bitwise."""
    kk = jax.random.split(jax.random.PRNGKey(7), 2)
    # |w| in [0.5, 1]: every magnitude bit appears in every 256x128 tile
    w = (jnp.sign(jax.random.normal(kk[0], (512, 128)))
         * (0.5 + 0.5 * jax.random.uniform(kk[1], (512, 128))))
    a = jax.random.normal(jax.random.PRNGKey(8), (8, 512))
    kw = knead(w, bits=8, ks=256, n_block=128)
    assert kw.schedule.total_work == kw.schedule.dense_work(kw.bits)
    assert kw.schedule.num_work == kw.k // kw.ks
    assert kw.schedule.planes_per_step == kw.bits - 1
    out_planes = sac_matmul(a, kw, impl="planes")
    out_pallas = sac_matmul(a, kw, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out_pallas),
                                  np.asarray(out_planes))


# ------------------------------------------------------- the K-tile walk
#
# One grid step per occupied K-tile, running that tile's present planes in
# its body.  The weight below has planes empty inside occupied K-tiles (a
# block 100x smaller than its column's scale has only low planes) and whole
# K-tiles empty in some N-tiles, so the walk's step count, its plane masks
# and its executed passes all differ from the dense walk's.

def _ktile_sparse_w():
    """[1024, 384] (4 K-tiles x 3 N-tiles): N-tile 0 with K-tile 3 empty;
    N-tile 1 with K-tile 1 empty and K-tile 2 small; N-tile 2 with K-tiles
    0 and 3 empty and K-tile 1 small."""
    w = np.array(_sparse_w(71, 1024, 384, 0.0))
    w[768:1024, 0:128] = 0.0
    w[256:512, 128:256] = 0.0
    w[512:768, 128:256] *= 0.01
    w[0:256, 256:384] = 0.0
    w[768:1024, 256:384] = 0.0
    w[256:512, 256:384] *= 0.01
    return jnp.asarray(w)


# 8-bit cases keep their bare ids; 4 and 16 bits stack 3 and 15 planes in
# one kernel body.
_WALK_CASES = ([pytest.param(8, m, id=str(m)) for m in (1, 8, 64, 1024)]
               + [pytest.param(b, m, id=f"{b}bit-{m}")
                  for b in (4, 16) for m in (1, 8, 64)])


@pytest.mark.parametrize("bits,m", _WALK_CASES)
def test_ktile_walk_sparse_bit_exact(bits, m):
    """pallas == planes bitwise at decode and prefill M; ``counts`` is the
    occupied K-tiles per N-tile and ``total_work`` (the popcount of the
    slots' plane masks) the occupancy nonzeros, at every width."""
    kw = knead(_ktile_sparse_w(), bits=bits, ks=256, n_block=128)
    occ = np.asarray(kw.occupancy_map())           # [bits - 1, 4, 3]
    occupied = occ.any(axis=0)
    assert not occupied.all()                  # wholly empty K-tiles
    counts = np.asarray(kw.schedule.counts)
    np.testing.assert_array_equal(counts, occupied.sum(axis=0))
    if bits == 8:
        assert not occ[:, occupied].all()      # empty planes in live K-tiles
        assert counts.tolist() == [3, 3, 2]
    assert kw.schedule.total_work == int(occ.sum())
    assert int(np.bitwise_count(np.asarray(kw.schedule.plane_bits)).sum()) \
        == int(occ.sum())
    a = jax.random.normal(jax.random.PRNGKey(m), (m, 1024))
    out = sac_matmul(a, kw, impl="pallas")
    ref = sac_matmul(a, kw, impl="planes")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("case,bits", [
    pytest.param("dense", 8, id="dense"),
    pytest.param("sparse", 8, id="sparse"),
    pytest.param("dense", 4, id="dense4"),
    pytest.param("dense", 16, id="dense16"),
])
def test_planes_per_step(case, bits):
    """MXU passes per executed grid step: all ``bits - 1`` planes on every
    occupied K-tile of a dense random weight, fewer where the weight-side
    skip empties planes."""
    w = (_sparse_w(72, 1024, 384, 0.0) if case == "dense"
         else _ktile_sparse_w())
    sched = knead(w, bits=bits, ks=256, n_block=128).schedule
    steps = int(np.asarray(sched.counts).sum())
    assert sched.planes_per_step == sched.total_work / steps
    if case == "dense":
        assert sched.planes_per_step == bits - 1
        assert steps == 4 * 3
    else:
        assert 1.0 < sched.planes_per_step < bits - 1


@pytest.mark.parametrize("case", ["all_zero", "all_dense", "single_hot"])
def test_ktile_walk_activation_extremes(case):
    """Activation extremes on the static walk: an all-zero, a dense and a
    single-hot (one live K-tile) activation all give pallas == planes
    bitwise, and the all-zero one gives exact zeros."""
    kw = knead(_sparse_w(31, 1024, 256, 0.5), bits=8)
    rng = np.random.default_rng(32)
    a = np.zeros((2, 1024), np.float32)
    if case == "all_dense":
        a = rng.normal(size=(2, 1024)).astype(np.float32)
    elif case == "single_hot":
        a[:, 256:512] = rng.normal(size=(2, 256)).astype(np.float32)
    a = jnp.asarray(a)
    out = np.asarray(sac_matmul_pallas(a, kw, bm=8))
    ref = np.asarray(sac_matmul(a, kw, impl="planes"))
    np.testing.assert_array_equal(out[:, :kw.logical_n], ref)
    if case == "all_zero":
        np.testing.assert_array_equal(out, np.zeros_like(out))
    else:
        assert np.abs(out).max() > 0


@pytest.mark.parametrize("partition,stacked,bits", [
    pytest.param(p, s, 8, id=f"{p}-{'stacked' if s else '2d'}")
    for p in ("contiguous", "balanced") for s in (False, True)] + [
    pytest.param("contiguous", False, b, id=f"contiguous-2d-{b}bit")
    for b in (4, 16)])
def test_ktile_walk_sharded_replays_unsharded(partition, stacked, bits):
    """Sharded walks (both partitions, and 3- and 15-plane bodies), one
    layer of a stacked weight, and a stacked weight sharded per layer, all
    replay the unsharded kernel on the sparse K-tile weight bit-exactly."""
    w = _ktile_sparse_w()
    a = jax.random.normal(jax.random.PRNGKey(73), (8, 1024))
    kw = knead(w, bits=bits, ks=256, n_block=128)
    ref = np.asarray(sac_matmul_pallas(a, kw, bm=8))
    if stacked:
        # layer 1 is dense, so the stack pads layer 0's walks to its width
        stack = knead_stacked(jnp.stack([w, _sparse_w(74, 1024, 384, 0.0)]),
                              bits=8, ks=256, n_block=128)
        assert stack.schedule.num_work > kw.schedule.num_work
        layer0 = jax.tree.map(lambda x: x[0], stack)
        np.testing.assert_array_equal(
            np.asarray(sac_matmul_pallas(a, layer0, bm=8)), ref)
        skw = jax.tree.map(lambda x: x[0],
                           shard_stacked_schedule(stack, 2,
                                                  partition=partition))
    else:
        skw = shard_schedule(kw, 2, partition=partition)
    out = sac_matmul_pallas_sharded(a, skw, bm=8)[:, :kw.n]
    np.testing.assert_array_equal(np.asarray(out), ref)


# ------------------------------- balanced partitioner (docs/DESIGN.md §11)
#
# Load properties run on crafted occupancy maps (``with_occupancy`` installs
# them over an all-zero weight — shard accounting reads the schedule only, so
# no execution is needed); bit-exactness properties run real sparse weights
# through the gathered sharded kernel against the unsharded one.

def _occ_kw(occ):
    """A minimal kneaded weight carrying a crafted occupancy map."""
    nb, nk, nn = occ.shape
    w = jnp.zeros((nk * 256, nn * 128))
    return knead(w, bits=nb + 1, ks=256, n_block=128).with_occupancy(
        jnp.asarray(occ))


def _check_balanced_properties(occ, shards):
    kw = _occ_kw(occ)
    cont = shard_schedule(kw, shards)
    bal = shard_schedule(kw, shards, partition="balanced")
    total = shards * bal.tiles_per_shard
    # balanced never loads its worst shard more than contiguous
    assert max(bal.shard_work) <= max(cont.shard_work)
    # work is conserved: both partitions carry every occupancy nonzero
    assert sum(bal.shard_work) == sum(cont.shard_work) == int(occ.sum())
    # tile_slot is a bijection covering all (real + padding) N-tiles
    slot = np.asarray(bal.tile_slot)
    assert sorted(slot.tolist()) == list(range(total))
    # contiguous mode records the identity permutation
    np.testing.assert_array_equal(np.asarray(cont.tile_slot),
                                  np.arange(total))
    # the packed counts really sit where tile_slot says they do
    packed = np.asarray(bal.counts).reshape(-1)
    orig = np.asarray(kw.schedule.counts)
    for j in range(orig.size):
        assert packed[slot[j]] == orig[j]
    # both partitions verify clean against their shard-time checksums
    assert not bal.verify() and not cont.verify()


@given(seed=st.integers(0, 1000),
       shards=st.sampled_from([2, 3, 4]),
       nn=st.integers(2, 12),
       density=st.sampled_from([0.1, 0.4, 0.9]))
def test_balanced_partition_properties(seed, shards, nn, density):
    """PROPERTY: for random occupancy maps, balanced ``max(shard_work)`` <=
    contiguous, tile_slot is a bijection over all N-tiles, and totals are
    conserved — including N-tile counts that don't divide the shard count
    (padding tiles join the packing)."""
    rng = np.random.default_rng(seed)
    occ = (rng.random((7, 1, nn)) < density).astype(np.int32)
    _check_balanced_properties(occ, shards)


def test_balanced_partition_properties_smoke():
    """Non-hypothesis fallback of the partitioner property: fixed skewed and
    adversarial cases run in every environment."""
    rng = np.random.default_rng(3)
    for nn, shards in ((8, 4), (5, 2), (7, 3), (16, 4)):
        occ = (rng.random((7, 1, nn)) < 0.4).astype(np.int32)
        _check_balanced_properties(occ, shards)


def test_balanced_never_worse_than_optimal_contiguous():
    """The greedy LPT packing alone can LOSE to a contiguous layout that
    happens to be optimal (LPT is a 4/3-approximation): per-tile passes
    [3,3,0,2,2,2] at 2 shards pack greedily to max 7 while the contiguous
    slabs hit the optimal 6.  Balanced mode must take the better of the
    two — pinned here so the property above can never regress."""
    occ = np.zeros((7, 1, 6), np.int32)
    for j, c in enumerate([3, 3, 0, 2, 2, 2]):
        occ[:c, 0, j] = 1
    kw = _occ_kw(occ)
    cont = shard_schedule(kw, 2)
    bal = shard_schedule(kw, 2, partition="balanced")
    assert max(cont.shard_work) == 6          # contiguous is optimal here
    assert max(bal.shard_work) == 6           # balanced must match it
    np.testing.assert_array_equal(np.asarray(bal.tile_slot), np.arange(6))


def _extreme_weight(case):
    k, nn = 512, 3                            # 3 N-tiles: N % 2 != 0 too
    if case == "all_empty":
        return jnp.zeros((k, nn * 128))
    if case == "all_dense":
        kk = jax.random.split(jax.random.PRNGKey(20), 2)
        return (jnp.sign(jax.random.normal(kk[0], (k, nn * 128)))
                * (0.5 + 0.5 * jax.random.uniform(kk[1], (k, nn * 128))))
    if case == "single_hot":
        w = jnp.zeros((k, nn * 128))
        hot = jax.random.normal(jax.random.PRNGKey(21), (k, 128)) * 0.05
        return w.at[:, 128:256].set(hot)
    if case == "ragged_sparse":
        return _sparse_w(22, k, nn * 128, sparsity=0.8)
    raise AssertionError(case)


@pytest.mark.parametrize("shards", [2, 3, 4])
@pytest.mark.parametrize("case", ["all_empty", "all_dense", "single_hot",
                                  "ragged_sparse"])
def test_balanced_sharded_bit_exact_extremes(case, shards):
    """PROPERTY (fixed extremes): balanced-sharded output, gathered back
    through tile_slot, is bit-exact against the unsharded Pallas kernel at
    every sparsity extreme — all-empty (zero work anywhere), all-dense
    (permutation of a full schedule), one hot tile (maximal skew), ragged
    sparse with N-tiles not dividing the shard count."""
    w = _extreme_weight(case)
    a = jax.random.normal(jax.random.PRNGKey(23), (8, 512))
    kw = knead(w, bits=8, ks=256, n_block=128)
    skw = shard_schedule(kw, shards, partition="balanced")
    out = sac_matmul_pallas_sharded(a, skw, bm=8)[:, :kw.n]
    ref = sac_matmul_pallas(a, kw, bm=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    if case == "single_hot" and shards >= 3:
        # maximal skew: one tile holds ALL the work — no partition can
        # spread it, but balanced must not make it worse
        assert max(skw.shard_work) == skw.total_work


@given(seed=st.integers(0, 50), shards=st.sampled_from([2, 3, 4]))
def test_balanced_sharded_bit_exact_random(seed, shards):
    """PROPERTY: random column-structured sparsity → balanced-sharded ==
    unsharded, bitwise (the gather restores original column order and each
    tile's f32 accumulation sequence is untouched)."""
    rng = np.random.default_rng(seed)
    w = np.array(_sparse_w(seed, 512, 512, sparsity=0.5))   # writable copy
    # zero random whole N-blocks so tiles carry genuinely unequal work
    for j in range(4):
        if rng.random() < 0.5:
            w[:, j * 128:(j + 1) * 128] = 0.0
    a = jax.random.normal(jax.random.PRNGKey(seed + 7), (8, 512))
    kw = knead(jnp.asarray(w), bits=8)
    skw = shard_schedule(kw, shards, partition="balanced")
    out = sac_matmul_pallas_sharded(a, skw, bm=8)[:, :kw.n]
    ref = sac_matmul_pallas(a, kw, bm=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -------------------------------------------------- logical-K direct calls
def test_sac_matmul_pallas_accepts_logical_k():
    """Direct FC callers pass logical-K activations; padding happens inside
    (mirrors sac_conv2d) and parity with the oracle stays bit-exact."""
    w = jax.random.normal(jax.random.PRNGKey(3), (300, 100)) * 0.05
    a = jax.random.normal(jax.random.PRNGKey(4), (8, 300))
    kw = knead_padded(w, bits=8, ks=256)
    assert kw.k != 300                          # really padded
    out = sac_matmul_pallas(a, kw, bm=8)        # logical K accepted
    assert out.shape == (8, kw.n)
    ref = sac_matmul(a, kw, impl="planes")      # sliced to logical N
    np.testing.assert_array_equal(np.asarray(out[:, :100]), np.asarray(ref))
    try:
        sac_matmul_pallas(jax.random.normal(jax.random.PRNGKey(5), (8, 299)),
                          kw, bm=8)
    except ValueError as e:
        assert "neither" in str(e)
    else:
        raise AssertionError("mismatched K must raise")
