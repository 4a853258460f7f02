"""Kernel-level benchmarks: SAC bit-plane matmul + kneaded integer GEMM.

Wall-times here are interpret-mode (CPU container) — meaningful only as
correctness-path cost; the TPU-relevant derived metrics are the HBM byte
ratios and the *executed-vs-dense tile-dot accounting* of the compacted
schedule (what the roofline and the CI regression gate consume).

The ``alexnet_sweep`` section kneads every AlexNet layer (weights trained
briefly from a fixed seed under the pinned jax — deterministic, see
:func:`alexnet_sweep`) and reports, per layer, the MXU passes the schedule
actually dispatches (``executed_tile_dots == occupancy nonzeros`` —
asserted here) against the dense grid's ``(B-1) * K/bk * N/bn``, plus the
paper's kneaded cycle ratio.

The ``sharded_sweep`` section partitions those same schedules over 4 model
shards (docs/DESIGN.md §5) and reports per-shard executed work and the
max/mean imbalance — deterministic, so ``shard_executed_max`` joins the CI
regression gate.  ``decode_sweep`` runs the kernel's decode-GEMV fast path
(docs/DESIGN.md §7) at batch 1/8/32 — tokens/s reported, the deterministic
tile-dot counts and max-error gated.  ``sharded_decode_sweep`` runs the LM
serving regime over sharded *stacked* schedules (docs/DESIGN.md §8):
batch 1/8 x shards 1/2/4 on a two-layer column-sparse projection bank,
per-shard work + imbalance reported, tile-dots/critical-path-load/max-err
gated.  ``moe_decode_sweep`` routes a fixed skewed trace through the
kneaded per-expert decode-GEMV path (docs/DESIGN.md §13): runtime-masked
executed tile-dots vs the dense expert slab, static expert imbalance, and
the emulated expert-parallel-vs-all-local max-err (0.0) are gated; the
derived string names how many experts the trace leaves active.
``serving`` runs the batched submit()/drain() front end on an
AlexNet-16 engine and reports per-request latency (wall clock: reported,
not gated).  ``serving_load_sweep`` replays a fixed Poisson request trace
against the LM engine's batch vs continuous schedulers (docs/DESIGN.md §9)
in deterministic tick space — latency-in-ticks p50/p95 and total ticks are
gated, wall tokens/s reported.

``--quick`` shrinks the raw-kernel shapes/bit sweeps to CI-smoke size (the
AlexNet sweep is metadata-only and always runs); ``--json PATH`` writes the
rows *with structured metrics* as JSON — the per-PR perf artifact that
``benchmarks/check_regression.py`` gates against the committed baseline.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import cnn_weights, timed
from repro.core import knead, quantize
from repro.core.kneading import knead_padded, kneading_ratio
from repro.kernels.kneaded_gemm.ops import kneaded_gemm
from repro.kernels.kneaded_gemm.ref import pack_int4
from repro.kernels.sac_matmul.ops import m_block, sac_matmul_pallas
from repro.kernels.sac_matmul.ref import sac_matmul_ref

# (name, us_per_call, derived-string, structured metrics for the JSON gate)
BenchRow = Tuple[str, float, str, Dict[str, float]]


def _schedule_metrics(kw) -> Dict[str, float]:
    """Compacted-schedule accounting for one kneaded weight."""
    sched = kw.schedule
    occ_nnz = int(np.asarray(kw.occupancy_map()).sum())
    executed = sched.total_work
    # the bench is self-checking: the schedule must dispatch exactly the
    # occupied tiles — executed == occupancy nonzeros, NOT (B-1)*K/bk*N/bn
    assert executed == occ_nnz, (executed, occ_nnz)
    dense = sched.dense_work(kw.bits)
    return {
        "executed_tile_dots": executed,
        "dense_tile_dots": dense,
        "occupancy_nonzeros": occ_nnz,
        "tile_dot_skip_frac": 1.0 - executed / max(1, dense),
        "metadata_bytes": kw.metadata_bytes(),
        "bytes_vs_bf16": kw.packed_bytes() / kw.dense_bf16_bytes(),
    }


def sac_rows(quick: bool) -> List[BenchRow]:
    rows: List[BenchRow] = []
    key = jax.random.PRNGKey(0)
    m, k, n = (8, 256, 128) if quick else (8, 1024, 512)
    w = jax.random.normal(key, (k, n)) * 0.02
    a = jax.random.normal(jax.random.PRNGKey(1), (m, k))

    for bits in (4, 8) if quick else (4, 8, 16):
        kw = knead(w, bits=bits, ks=256, n_block=128)
        us, out = timed(lambda: sac_matmul_pallas(a, kw, bm=8), repeats=1)
        ref = sac_matmul_ref(a, kw)
        err = float(jnp.max(jnp.abs(out - ref)))
        met = _schedule_metrics(kw)
        met["max_err"] = err
        rows.append((
            f"kernel/sac_matmul_b{bits}", us,
            f"bytes_vs_bf16={met['bytes_vs_bf16']:.3f} "
            f"tile_dots={met['executed_tile_dots']}/{met['dense_tile_dots']} "
            f"max_err={err:.1e}", met))

    qt8 = quantize(w, bits=8)
    us, out8 = timed(lambda: kneaded_gemm(a, qt8.q, qt8.scale.reshape(1, -1)),
                     repeats=1)
    err8 = float(jnp.max(jnp.abs(out8 - a @ (qt8.q * qt8.scale))))
    rows.append(("kernel/kneaded_gemm_int8", us,
                 f"weight_bytes_vs_bf16=0.500 max_err={err8:.1e}",
                 {"max_err": err8}))

    qt4 = quantize(w, bits=4)
    packed = pack_int4(qt4.q)
    us, out4 = timed(lambda: kneaded_gemm(a, packed, qt4.scale.reshape(1, -1),
                                          packed4=True), repeats=1)
    err4 = float(jnp.max(jnp.abs(out4 - a @ (qt4.q * qt4.scale))))
    rows.append(("kernel/kneaded_gemm_int4", us,
                 f"weight_bytes_vs_bf16=0.250 max_err={err4:.1e}",
                 {"max_err": err4}))

    # dense bf16 reference timing (XLA, not interpret — not comparable, but
    # shows the oracle cost scale)
    us, _ = timed(lambda: a.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16))
    rows.append(("kernel/dense_bf16_xla_ref", us, "baseline_matmul", {}))
    return rows


def _blocksparse_fc8(params, ks: int) -> jax.Array:
    """fc8 with its 50% lowest-L2 (ks x 128) blocks pruned — the shared
    block-sparse specimen of both gated sweeps (the pruning recipe must not
    drift between the unsharded and sharded baseline rows)."""
    from repro.models import cnn

    w = jnp.asarray(cnn.weight_matrices(params)["fc8"])     # [4096, 1024]
    kb, nb = w.shape[0] // ks, w.shape[1] // 128
    blocks = w.reshape(kb, ks, nb, 128)
    norms = jnp.sqrt(jnp.sum(blocks ** 2, axis=(1, 3)))     # [kb, nb]
    mask = norms >= jnp.median(norms)
    return (blocks * mask[:, None, :, None]).reshape(w.shape)


def alexnet_sweep(bits: int = 8, ks: int = 256,
                  cycle_ks: int = 16) -> List[BenchRow]:
    """Per-layer compacted-schedule accounting on trained AlexNet weights.

    Metadata-only (no kernel execution): kneads each conv/fc im2col matrix
    and reports executed vs dense tile-dots plus the Fig 11 kneaded cycle
    ratio at hardware stride ``cycle_ks``.  Deterministic: ``cnn_weights``
    trains briefly from a fixed seed under the *pinned* jax version (~3s on
    a cache miss, cached to benchmarks/artifacts/ afterwards), so fresh CI
    checkouts reproduce the same weights the committed baseline was built
    from; the 10% gate tolerance absorbs any cross-ISA float drift.
    """
    from repro.models import cnn

    rows: List[BenchRow] = []
    params = cnn_weights("alexnet")
    for lname, w in cnn.weight_matrices(params).items():
        w = jnp.asarray(w)
        kw = knead_padded(w, bits=bits, ks=ks)
        met = _schedule_metrics(kw)
        q = quantize(w, bits=bits, axis=None).q
        k16 = (q.shape[0] // cycle_ks) * cycle_ks
        met["cycle_ratio"] = float(kneading_ratio(q[:k16], bits, cycle_ks))
        rows.append((
            f"alexnet_sweep/{lname}", 0.0,
            f"tile_dots={met['executed_tile_dots']}/{met['dense_tile_dots']} "
            f"skip={100 * met['tile_dot_skip_frac']:.1f}% "
            f"cycle_ratio={100 * met['cycle_ratio']:.1f}% "
            f"shape={tuple(w.shape)}", met))
    total_exec = sum(r[3]["executed_tile_dots"] for r in rows)
    total_dense = sum(r[3]["dense_tile_dots"] for r in rows)
    rows.append((
        "alexnet_sweep/total", 0.0,
        f"tile_dots={total_exec}/{total_dense} "
        f"skip={100 * (1 - total_exec / total_dense):.1f}%",
        {"executed_tile_dots": total_exec, "dense_tile_dots": total_dense}))

    # Dense trained weights occupy every (ks x n_block) tile — the schedule
    # degenerates to the dense grid there (executed == dense, as the rows
    # above show).  Block-structured sparsity at the kernel's own skip
    # granularity is where compaction bites: prune the 50% lowest-L2
    # (256 x 128) blocks of fc8 and the schedule dispatches ~half the MXU
    # passes, which the CI gate then pins.
    kw = knead_padded(_blocksparse_fc8(params, ks), bits=bits, ks=ks)
    met = _schedule_metrics(kw)
    rows.append((
        "alexnet_sweep/fc8_blocksparse50", 0.0,
        f"tile_dots={met['executed_tile_dots']}/{met['dense_tile_dots']} "
        f"skip={100 * met['tile_dot_skip_frac']:.1f}% "
        f"(block-pruned at the kernel's ks x n_block skip granularity)", met))
    rows += _act_skip_rows(params, bits=bits, ks=ks)
    return rows


def _relu_sparse_trace(seed: int, k: int, ks: int,
                       dead_frac: float = 0.5) -> jax.Array:
    """A deterministic decode-GEMV activation row with ReLU + dead-channel
    structure: elementwise ReLU sparsity alone (~50% zeros) never empties a
    ``ks``-wide K-tile, so tile-granular runtime skip sees nothing — the
    payoff comes from *dead channels* (whole feature maps stuck at zero in
    trained ReLU nets), modeled here by zeroing ``dead_frac`` of the K-tiles
    wholesale."""
    kk = jax.random.split(jax.random.PRNGKey(seed), 2)
    a = jax.nn.relu(jax.random.normal(kk[0], (1, k)))
    nk = k // ks
    alive = (jax.random.uniform(kk[1], (nk,)) >= dead_frac).astype(a.dtype)
    return (a.reshape(1, nk, ks) * alive[None, :, None]).reshape(1, k)


def _act_skip_metrics(kw, a: jax.Array) -> Dict[str, float]:
    """Two-sided (weight x activation) tile-dot accounting for one decode
    row.  NOT :func:`_schedule_metrics`: with the runtime intersection the
    executed count sits *below* the occupancy nonzeros, which is the point."""
    from repro.core import activation_occupancy as actocc

    pres = actocc.ktile_presence(a, kw.ks)
    mask = actocc.work_mask(kw.schedule.plane_bits, kw.schedule.ktile_ids,
                            pres)
    executed = int(np.asarray(actocc.mask_passes(mask)))
    weight_only = int(kw.schedule.total_work)
    dense = int(kw.schedule.dense_work(kw.bits))
    return {
        "executed_tile_dots": executed,
        "weight_tile_dots": weight_only,
        "dense_tile_dots": dense,
        "act_skip_frac": 1.0 - executed / max(1, weight_only),
        "tile_dot_skip_frac": 1.0 - executed / max(1, dense),
    }


def _act_skip_rows(params, bits: int, ks: int) -> List[BenchRow]:
    """Activation-intersected rows on trained AlexNet fc layers
    (docs/DESIGN.md §12).  Dense trained weights occupy every tile, so the
    weight-only rows above report skip=0.0% on the fc layers — the honesty
    gap of one-sided kneading.  Against a ReLU-sparse decode trace the
    runtime intersection drops the dead channels' tile-dots, so these rows
    report ``tile_dot_skip_frac > 0`` on the SAME dense weights (asserted,
    plus strict executed < weight-only — and bit-exactness on the row that
    runs the masked kernel); ``act_skip_frac`` joins the higher-is-better
    CI gate."""
    from repro.core.sac import sac_matmul
    from repro.models import cnn

    rows: List[BenchRow] = []
    wmats = cnn.weight_matrices(params)
    # (row suffix, weight, run the masked kernel?) — fc10 is small enough
    # to pay interpret-mode kernel parity at bench time; fc8 rows are
    # accounting-only (the test wall owns their bit-exactness)
    cases = (("fc8_actskip", jnp.asarray(wmats["fc8"]), 31, False),
             ("fc8_blocksparse50_actskip", _blocksparse_fc8(params, ks),
              31, False),
             # seed 30 leaves fc10's 4 K-tiles half alive — a *partial*
             # mask, so the kernel row exercises mixed survive/drop steps
             ("fc10_actskip", jnp.asarray(wmats["fc10"]), 30, True))
    for suffix, w, seed, run_kernel in cases:
        kw = knead_padded(w, bits=bits, ks=ks)
        a = _relu_sparse_trace(seed, kw.k, ks)
        met = _act_skip_metrics(kw, a)
        # the two-sided accounting must actually bite on dense weights
        assert met["executed_tile_dots"] < met["weight_tile_dots"], \
            (suffix, met)
        assert met["tile_dot_skip_frac"] > 0.0, (suffix, met)
        derived = (
            f"tile_dots={met['executed_tile_dots']}"
            f"/{met['weight_tile_dots']}(w-only)"
            f"/{met['dense_tile_dots']}(dense) "
            f"act_skip={100 * met['act_skip_frac']:.1f}%")
        if run_kernel:
            us, out = timed(
                lambda: sac_matmul_pallas(a, kw, skip_activations=True),
                repeats=1)
            ref = np.asarray(sac_matmul(a, kw, impl="planes"))
            err = float(np.max(np.abs(
                np.asarray(out)[:, :kw.logical_n] - ref)))
            assert err == 0.0, (suffix, err)     # masked walk is bit-exact
            met["max_err"] = err
            derived += f" max_err={err:.1e}"
        else:
            us = 0.0
        rows.append((f"alexnet_sweep/{suffix}", us, derived, met))
    return rows


def sharded_sweep(num_shards: int = 4, bits: int = 8,
                  ks: int = 256) -> List[BenchRow]:
    """Per-layer N-sharded schedule accounting on trained AlexNet weights.

    Metadata-only, like :func:`alexnet_sweep`: shards every layer's
    compacted schedule over ``num_shards`` (a plain shard count — no mesh
    needed for load accounting) and reports each device's executed work.
    ``shard_executed_max`` is the critical-path load (the slowest device
    gates the layer), ``shard_imbalance`` = max/mean; both deterministic
    from the committed weights, so the regression gate pins the max.
    """
    from repro.core.schedule import shard_schedule
    from repro.models import cnn

    rows: List[BenchRow] = []
    params = cnn_weights("alexnet")
    for lname, w in cnn.weight_matrices(params).items():
        kw = knead_padded(jnp.asarray(w), bits=bits, ks=ks)
        skw = shard_schedule(kw, num_shards)
        imb = skw.imbalance()
        met = {
            "executed_tile_dots": skw.total_work,
            "dense_tile_dots": skw.dense_work(),
            "shard_executed_max": imb["max"],
            "shard_imbalance": imb["imbalance"],
        }
        rows.append((
            f"sharded_sweep/{lname}@{num_shards}", 0.0,
            f"shard_work={imb['shard_work']} "
            f"imbalance={imb['imbalance']:.2f}", met))

    # block-sparse fc8 (the alexnet_sweep row where compaction bites):
    # pruning is occupancy-blind to shard boundaries, so this is the
    # imbalance stress case the report exists for
    skw = shard_schedule(
        knead_padded(_blocksparse_fc8(params, ks), bits=bits, ks=ks),
        num_shards)
    imb = skw.imbalance()
    rows.append((
        f"sharded_sweep/fc8_blocksparse50@{num_shards}", 0.0,
        f"shard_work={imb['shard_work']} imbalance={imb['imbalance']:.2f}",
        {"executed_tile_dots": skw.total_work,
         "shard_executed_max": imb["max"],
         "shard_imbalance": imb["imbalance"]}))
    return rows


def decode_sweep(quick: bool) -> List[BenchRow]:
    """Decode-GEMV rows: the SAC kernel in the LM decode regime (M = batch).

    Runs ``sac_matmul_pallas`` at batch 1/8/32 on a fixed-seed LM-projection
    -sized kneaded weight — the ops-layer fast path shrinks the M block to
    the 8-row sublane floor instead of padding a one-token step to the full
    streamed block.  ``tokens_per_s`` is interpret-mode wall clock (reported,
    not gated); the deterministic ``executed_tile_dots`` and ``max_err`` of
    each row join the CI regression gate, so a change that inflates the
    decode path's dispatched MXU passes (or its accuracy) fails the build.
    """
    rows: List[BenchRow] = []
    k, n = (256, 128) if quick else (1024, 512)
    w = jax.random.normal(jax.random.PRNGKey(11), (k, n)) * 0.02
    kw = knead(w, bits=8, ks=256, n_block=128)
    for batch in (1, 8, 32):
        a = jax.random.normal(jax.random.PRNGKey(12), (batch, k))
        us, out = timed(lambda: sac_matmul_pallas(a, kw), repeats=1)
        err = float(jnp.max(jnp.abs(out - sac_matmul_ref(a, kw))))
        tok_s = batch / (us * 1e-6)
        bm_eff = m_block(batch)     # the fast path the kernel actually ran
        met = _schedule_metrics(kw)
        met["max_err"] = err
        met["tokens_per_s"] = tok_s          # wall clock: not gated
        rows.append((
            f"decode_sweep/gemv_b{batch}", us,
            f"tok_s={tok_s:.1f} bm_eff={bm_eff} "
            f"tile_dots={met['executed_tile_dots']}/{met['dense_tile_dots']} "
            f"max_err={err:.1e}", met))

    # activation-skip decode row (docs/DESIGN.md §12): an LM-projection-
    # sized kneaded weight driven by a ReLU-sparse single-token trace
    # through the masked kernel walk — executed tile-dots drop strictly
    # below the weight-only schedule at zero error (both asserted;
    # act_skip_frac joins the higher-is-better CI gate).  Fixed at
    # K=1024 even under --quick: the quick shapes have a single K-tile,
    # where tile-granular skip is all-or-nothing
    from repro.core.sac import sac_matmul

    k, n = 1024, 512
    w = jax.random.normal(jax.random.PRNGKey(11), (k, n)) * 0.02
    kw = knead(w, bits=8, ks=256, n_block=128)
    a = _relu_sparse_trace(32, k, 256)
    met = _act_skip_metrics(kw, a)
    us, out = timed(lambda: sac_matmul_pallas(a, kw, skip_activations=True),
                    repeats=1)
    err = float(np.max(np.abs(np.asarray(out)
                              - np.asarray(sac_matmul(a, kw,
                                                      impl="planes")))))
    assert err == 0.0, err
    assert met["executed_tile_dots"] < met["weight_tile_dots"], met
    met["max_err"] = err
    met["tokens_per_s"] = 1 / (us * 1e-6)        # wall clock: not gated
    rows.append((
        "decode_sweep/gemv_b1_actskip", us,
        f"tok_s={met['tokens_per_s']:.1f} "
        f"tile_dots={met['executed_tile_dots']}"
        f"/{met['weight_tile_dots']}(w-only) "
        f"act_skip={100 * met['act_skip_frac']:.1f}% max_err={err:.1e}", met))
    return rows


def sharded_decode_sweep(quick: bool) -> List[BenchRow]:
    """Sharded decode-GEMV rows: the LM serving regime over a model mesh.

    A fixed-seed stacked [L, K, N] projection bank (two layers, 16 N-tiles,
    whole column blocks zeroed per layer so the *low* slabs hold all the
    live tiles — the contiguous split's worst case) is kneaded per layer
    (``knead_stacked``) and sharded at 1/2/4 under both tile->shard
    partitionings (``shard_stacked_schedule(..., partition=...)``,
    docs/DESIGN.md §11), then decoded through the scan-sliced serial shard
    walk at batch 1/8 — the exact per-layer kernel programs the mesh
    launches, minus the device transport, so the rows run on the single-CPU
    CI container.  ``tokens_per_s`` is the *unsharded* interpret wall clock
    scaled by the critical-path share ``shard_executed_max / total_work``
    (a serial walk cannot show parallel speedup directly, and the S-call
    serial walk pays per-launch interpret overhead a real mesh would not;
    the scaling uses the same deterministic accounting the gate pins) —
    reported, not gated.
    The deterministic ``executed_tile_dots``, ``shard_executed_max``
    (critical-path load of the most-loaded device), ``shard_imbalance``
    (~1.0 baselined on the balanced rows), and ``max_err`` vs the unsharded
    stacked kernel on BOTH the pallas and planes paths (bit-exact: 0.0)
    join the CI regression gate.  The balanced@4 rows are additionally
    self-checking: imbalance <= 1.15, modeled tokens/s >= the shards=1 row,
    max_err == 0.0 — the ISSUE's acceptance criterion, asserted at bench
    time.
    """
    from repro.core.kneading import knead_stacked
    from repro.core.sac import sac_matmul
    from repro.core.schedule import shard_stacked_schedule

    rows: List[BenchRow] = []
    k = 256 if quick else 1024
    n, layers = 2048, 2          # 16 N-tiles: enough grain to pack at S=4
    w = jax.random.normal(jax.random.PRNGKey(21), (layers, k, n)) * 0.02
    # structured column sparsity, different per layer: layer 0 keeps N-tiles
    # 0-7 (first half of its output channels), layer 1 tiles 0-11 (three
    # quarters) — contiguous slabs pile all work on the low shards
    w = w.at[0, :, n // 2:].set(0.0)
    w = w.at[1, :, (3 * n) // 4:].set(0.0)
    stacked = knead_stacked(w, bits=8)

    def scan_decode(a, kw_stacked, impl="pallas"):
        def body(carry, kw_l):
            return carry, sac_matmul(a, kw_l, impl=impl)
        return jax.lax.scan(body, 0, kw_stacked)[1]

    base_us: Dict[int, float] = {}
    base_tok_s: Dict[int, float] = {}
    for batch in (1, 8):
        a = jax.random.normal(jax.random.PRNGKey(22), (batch, k))
        base_us[batch], _ = timed(lambda: scan_decode(a, stacked), repeats=1)
        base_tok_s[batch] = batch / (base_us[batch] * 1e-6)
    for shards, partition in ((1, "contiguous"), (2, "contiguous"),
                              (2, "balanced"), (4, "contiguous"),
                              (4, "balanced")):
        ssk = shard_stacked_schedule(stacked, shards, partition=partition)
        imb = ssk.imbalance()
        for batch in (1, 8):
            a = jax.random.normal(jax.random.PRNGKey(22), (batch, k))
            us, out = timed(lambda: scan_decode(a, ssk), repeats=1)
            # bit-exact against the unsharded stack on BOTH reference paths
            err = max(
                float(jnp.max(jnp.abs(out - scan_decode(a, stacked)))),
                float(jnp.max(jnp.abs(
                    out - scan_decode(a, stacked, impl="planes")))))
            # modeled critical-path throughput: the unsharded wall clock
            # scaled by the most-loaded shard's share of the executed work
            crit = imb["max"] / max(1, ssk.total_work)
            tok_s = batch / (base_us[batch] * 1e-6 * max(crit, 1e-9))
            if partition == "balanced":
                assert err == 0.0, (shards, batch, err)
                if shards == 4:
                    assert imb["imbalance"] <= 1.15, imb
                    assert tok_s >= base_tok_s[batch], \
                        (tok_s, base_tok_s[batch])
            met = {
                "executed_tile_dots": ssk.total_work,
                "dense_tile_dots": ssk.dense_work(),
                "shard_executed_max": imb["max"],
                "shard_imbalance": imb["imbalance"],
                "max_layer_imbalance": imb.get("max_layer_imbalance", 1.0),
                "max_err": err,
                "tokens_per_s": tok_s,       # wall-clock-derived: not gated
            }
            rows.append((
                f"sharded_decode_sweep/b{batch}@s{shards}/{partition}", us,
                f"tok_s={tok_s:.1f} shard_work={imb['shard_work']} "
                f"imbalance={imb['imbalance']:.2f} max_err={err:.1e}", met))
    return rows


def moe_decode_sweep(quick: bool) -> List[BenchRow]:
    """Kneaded expert-parallel MoE decode rows (docs/DESIGN.md §13).

    A fixed-seed SKEWED expert bank (8 experts; expert e keeps only a
    shrinking prefix of its N-tiles, so the static per-expert work table is
    heavily imbalanced) is kneaded per expert (``knead_stacked`` on
    [E, K, N]) and driven through the routed per-expert decode-GEMV path
    (``models.blocks._dispatch_compute_kneaded``) on a HANDCRAFTED skewed
    routing trace — deterministic token->expert assignments that leave half
    the experts without a single routed token.  The two-sided skip then
    turns routing sparsity into skipped MXU passes: an expert with no
    routed tokens gathers only the zero pad row, its activation presence is
    all-zero, and its entire schedule walk is masked off.

    Gated metrics (CI): ``executed_tile_dots`` (runtime-masked passes,
    asserted STRICTLY below the dense expert slab's tile-dot count — the
    ISSUE acceptance), ``expert_imbalance`` (static work-table max/mean —
    the load-skew signal expert placement has to live with), and
    ``max_err`` — the emulated expert-parallel run (per-shard expert slices
    dispatched at their global offsets, partials summed like the mesh
    psum) against the all-experts-local oracle, asserted == 0.0 at bench
    time for EP ∈ {2, 4}.  Reported honestly: the derived string names how
    many experts the trace leaves active — a capped trace *overstates*
    skip on traffic that actually spreads across all experts.
    """
    from repro.configs.base import ModelConfig
    from repro.core import activation_occupancy
    from repro.core.kneading import knead_stacked
    from repro.models import blocks

    e, bits = 8, 8
    k = 256 if quick else 512
    f = 256 if quick else 512
    cfg = ModelConfig(name="bench-moe", family="moe", num_experts=e,
                      top_k=2, moe_dff=f, d_model=k, activation="gelu",
                      impl="pallas", activation_skip=True)
    wi = jax.random.normal(jax.random.PRNGKey(31), (e, k, f)) * 0.02
    wo = jax.random.normal(jax.random.PRNGKey(32), (e, f, k)) * 0.02
    # skewed static occupancy: expert i keeps ~(e - i)/e of its N-tiles
    for i in range(e):
        keep_i = max(1, ((e - i) * f) // e)
        wi = wi.at[i, :, keep_i:].set(0.0)
        keep_o = max(1, ((e - i) * k) // e)
        wo = wo.at[i, :, keep_o:].set(0.0)
    kwi = knead_stacked(wi, bits=bits)
    kwo = knead_stacked(wo, bits=bits)
    table = kwi.work_table() + kwo.work_table()          # static [E] load
    expert_imbalance = float(table.max() / max(table.mean(), 1e-9))
    dense = e * (kwi.schedule.dense_work(bits)
                 + kwo.schedule.dense_work(bits))

    # handcrafted skewed routing traces: experts 4..7 never see a token
    traces = {
        1: jnp.asarray([[0, 1]], jnp.int32),
        8: jnp.asarray([[0, 1], [0, 2], [1, 2], [0, 1],
                        [2, 3], [0, 1], [1, 3], [0, 2]], jnp.int32),
    }

    def dispatch(x2d, eids, gates, kwi_, kwo_, e_offset, cap):
        return blocks._dispatch_compute_kneaded(
            x2d, eids, gates, kwi_, kwo_, cfg=cfg, e_offset=e_offset,
            cap=cap, dtype=jnp.float32)

    rows: List[BenchRow] = []
    for batch, eids in traces.items():
        active = int(np.unique(np.asarray(eids)).size)
        if active < e:
            # bench honesty (satellite): a capped trace inflates skip
            print(f"[moe_decode_sweep] b{batch}: routing trace caps "
                  f"active experts at {active}/{e} — skip fractions below "
                  f"overstate a uniformly-routed workload")
        gates = jnp.full(eids.shape, 1.0 / eids.shape[1], jnp.float32)
        x2d = jax.random.normal(jax.random.PRNGKey(34), (batch, k))
        cap = blocks._capacity(batch, cfg)
        # skip accounting from exactly ONE dispatch — the counters are
        # process-global and timed() adds a warmup launch on top of its
        # repeats, which would multiply executed_tile_dots per run
        activation_occupancy.reset_skip_stats()
        y_local = dispatch(x2d, eids, gates, kwi, kwo, 0, cap)
        jax.block_until_ready(y_local)
        stats = activation_occupancy.skip_stats()
        executed = int(stats["executed_tile_dots"])
        us, _ = timed(
            lambda: dispatch(x2d, eids, gates, kwi, kwo, 0, cap),
            repeats=1)
        # the ISSUE acceptance: the routed kneaded path executes strictly
        # fewer tile-dots than the capacity-padded dense expert slab
        assert 0 < executed < dense, (executed, dense)

        # emulated expert parallelism: per-shard expert slices at their
        # global offsets, partials summed exactly like the mesh psum
        err = 0.0
        for shards in (2, 4):
            e_loc = e // shards
            y_ep = sum(
                dispatch(
                    x2d, eids, gates,
                    jax.tree.map(lambda a, s=s: a[s * e_loc:
                                                  (s + 1) * e_loc], kwi),
                    jax.tree.map(lambda a, s=s: a[s * e_loc:
                                                  (s + 1) * e_loc], kwo),
                    s * e_loc, cap)
                for s in range(shards))
            err = max(err, float(jnp.max(jnp.abs(y_ep - y_local))))
        assert err == 0.0, err

        tok_s = batch / (us * 1e-6)
        met = {
            "executed_tile_dots": executed,
            "weight_tile_dots": int(stats["weight_tile_dots"]),
            "dense_tile_dots": dense,
            "expert_imbalance": expert_imbalance,
            "active_experts": active,
            "max_err": err,
            "tokens_per_s": tok_s,           # wall clock: not gated
        }
        rows.append((
            f"moe_decode_sweep/b{batch}_e{e}_top2", us,
            f"tok_s={tok_s:.1f} tile_dots={executed}/{dense}(dense) "
            f"active={active}/{e} imbalance={expert_imbalance:.2f} "
            f"max_err={err:.1e}", met))
    return rows


def serving_rows(quick: bool) -> List[BenchRow]:
    """Batched submit()/drain() front end: per-request latency on a kneaded
    AlexNet-16 engine (int path — the production CPU impl; wall clock, so
    reported but not gated)."""
    import dataclasses

    from repro.inference.cnn_engine import CNNServingConfig, CNNServingEngine
    from repro.models import cnn

    cfg = dataclasses.replace(cnn.CNN_ZOO["alexnet"], image_size=16)
    # init (not the cached trained-at-32 weights): the 16px fc dims differ,
    # and latency is what this row measures, not schedule statistics
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    n_req = 6 if quick else 12
    eng = CNNServingEngine(cfg, params,
                           CNNServingConfig(impl="int", buckets=(2, 4)))
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (n_req, cfg.image_size, cfg.image_size, 3))
    eng.logits(xs[:4])                       # warm the bucket-4 compile
    eng.logits(xs[:2])                       # ... and bucket-2
    for i in range(n_req):
        eng.submit(xs[i])
    eng.drain()
    stats = eng.latency_stats()
    return [(
        "serving/batched_alexnet16_int8", stats["mean_ms"] * 1e3,
        f"req={stats['requests']} p50={stats['p50_ms']:.1f}ms "
        f"p95={stats['p95_ms']:.1f}ms fill={stats['mean_batch_fill']:.2f}",
        {"requests": stats["requests"],
         "mean_batch_fill": stats["mean_batch_fill"]})]


def serving_load_sweep(quick: bool) -> List[BenchRow]:
    """Latency under load: batch-synchronous drain() vs the continuous
    scheduler on an identical Poisson request trace.

    Arrivals are generated in **tick space** — the engines' virtual-launch
    clock (+1 per jitted prefill/decode) — with a fixed seed, so the whole
    sweep is deterministic: per-request ``latency_ticks`` p50/p95 and the
    trace's ``total_ticks`` join the CI regression gate, while wall-clock
    tokens/s is reported only.  Each rate drives both engines through the
    same (arrival tick, prompt len, budget) trace: the batch server drains
    a wave whenever requests are waiting (new arrivals during a wave queue
    for the next one — the wave barrier this sweep exists to price), the
    continuous server admits at step granularity.  The bench self-checks
    the ISSUE acceptance bar: at the highest arrival rate, continuous p95
    must not exceed batch p95.
    """
    from repro.configs.registry import get_config
    from repro.inference.engine import ServingConfig, ServingEngine
    from repro.models.lm import LanguageModel

    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    n_req = 8 if quick else 16
    plens = [6, 10, 4, 6]
    budgets = [4, 8, 2, 6]
    prompts = [jax.random.randint(jax.random.PRNGKey(100 + i),
                                  (plens[i % 4],), 0, cfg.vocab_size)
               for i in range(n_req)]

    def make_engine(scheduler):
        return ServingEngine(cfg, params, ServingConfig(
            max_len=32, impl="int", knead_min_dim=8, buckets=(1, 2, 4),
            scheduler=scheduler, max_inflight=4, kv_block=16))

    def trace_for(lam):
        rng = np.random.default_rng(1234 + lam)
        gaps = rng.poisson(lam, size=n_req)
        return np.cumsum(gaps).tolist()

    def drive_batch(eng, arrivals):
        i = 0
        while i < n_req:
            eng.ticks = max(eng.ticks, arrivals[i])
            while i < n_req and arrivals[i] <= eng.ticks:
                h = eng.submit(prompts[i], budgets[i % 4])
                h._req.submit_tick = arrivals[i]   # true arrival, not drain
                i += 1
            eng.drain()
        eng.drain()

    def drive_continuous(eng, arrivals):
        i = 0
        busy = False
        while i < n_req or busy:
            while i < n_req and arrivals[i] <= eng.ticks:
                h = eng.submit(prompts[i], budgets[i % 4])
                h._req.submit_tick = arrivals[i]
                i += 1
            if not busy and i < n_req and not eng._pending:
                eng.ticks = arrivals[i]            # idle: jump to arrival
                continue
            busy = eng.scheduler_step()

    rows: List[BenchRow] = []
    p95_by = {}
    for lam in (12, 6, 2):                         # mean interarrival ticks
        arrivals = trace_for(lam)
        for sched, drive in (("batch", drive_batch),
                             ("continuous", drive_continuous)):
            eng = make_engine(sched)
            t0 = time.perf_counter()     # stateful drive: no warmup call
            drive(eng, arrivals)
            us = (time.perf_counter() - t0) * 1e6
            lat = np.array([r["latency_ticks"] for r in eng._request_log])
            assert lat.size == n_req, (sched, lam, lat.size)
            toks = sum(budgets[i % 4] for i in range(n_req))
            met = {
                "p50_latency_ticks": float(np.percentile(lat, 50)),
                "p95_latency_ticks": float(np.percentile(lat, 95)),
                "total_ticks": float(eng.ticks),
                "tokens_per_s": toks / (us * 1e-6),   # wall: not gated
            }
            p95_by[(sched, lam)] = met["p95_latency_ticks"]
            rows.append((
                f"serving_load_sweep/{sched}@lam{lam}", us,
                f"p50={met['p50_latency_ticks']:.0f} "
                f"p95={met['p95_latency_ticks']:.0f}t "
                f"total={eng.ticks}t tok_s={met['tokens_per_s']:.1f}", met))
    # the acceptance bar: continuous beats the wave barrier at peak load
    assert p95_by[("continuous", 2)] <= p95_by[("batch", 2)], p95_by
    return rows


def serving_fault_sweep(quick: bool) -> List[BenchRow]:
    """Goodput + tail latency under injected fault rates (~0/1/5% of
    decode launch attempts) on the continuous scheduler with the fault
    policy armed (docs/DESIGN.md §10).

    Deterministic end to end: tick-space Poisson arrivals with a fixed
    seed, fault injection at fixed decode-attempt indices, and
    ``retry_backoff_s=0`` so recovery scheduling never consults the wall
    clock — ``failed_requests``, ``retries`` and the tick-space latency
    percentiles are exact across runs and join the CI regression gate
    (a fault-handling change that starts losing requests or retrying
    more trips the gate).  Wall-clock goodput (completed tokens/s, the
    paid-for metric under faults) is reported but not gated.  The rate-0
    row runs with the policy armed too, so it prices the NaN-guard +
    watchdog overhead against serving_load_sweep's unguarded continuous
    rows.
    """
    from repro.configs.registry import get_config
    from repro.inference.engine import ServingConfig, ServingEngine
    from repro.inference.resilience import (EngineFaultInjector,
                                            ServingFaultPolicy)
    from repro.models.lm import LanguageModel

    cfg = get_config("smollm-360m", smoke=True)
    params = LanguageModel(cfg).init(jax.random.PRNGKey(0))
    n_req = 8 if quick else 16
    plens = [6, 10, 4, 6]
    budgets = [4, 8, 2, 6]
    prompts = [jax.random.randint(jax.random.PRNGKey(100 + i),
                                  (plens[i % 4],), 0, cfg.vocab_size)
               for i in range(n_req)]
    rng = np.random.default_rng(4321)
    arrivals = np.cumsum(rng.poisson(4, size=n_req)).tolist()
    # ~rate of the ≈30 (quick) / ≈65 decode attempts the trace generates;
    # fixed indices, NOT sampled, so every run injects identically
    fault_plans = (("0pct", ()),
                   ("1pct", (8,) if quick else (25,)),
                   ("5pct", (5, 12, 19) if quick else (5, 12, 19, 33, 47)))

    rows: List[BenchRow] = []
    for label, fail_steps in fault_plans:
        pol = ServingFaultPolicy(
            max_retries=3, retry_backoff_s=0.0,
            injector=(EngineFaultInjector(fail_decode_steps=fail_steps)
                      if fail_steps else None))
        eng = ServingEngine(cfg, params, ServingConfig(
            max_len=32, impl="int", knead_min_dim=8, buckets=(1, 2, 4),
            scheduler="continuous", max_inflight=4, kv_block=16,
            fault_policy=pol))
        handles = []
        i = 0
        busy = False
        t0 = time.perf_counter()
        while i < n_req or busy:
            while i < n_req and arrivals[i] <= eng.ticks:
                h = eng.submit(prompts[i], budgets[i % 4])
                h._req.submit_tick = arrivals[i]
                handles.append(h)
                i += 1
            if not busy and i < n_req and not eng._pending:
                eng.ticks = arrivals[i]            # idle: jump to arrival
                continue
            busy = eng.scheduler_step()
        wall_s = time.perf_counter() - t0
        stats = eng.latency_stats()
        done_tokens = sum(h._req.num_tokens for h in handles
                          if h.state == "done")
        lat = np.array([r["latency_ticks"] for r in eng._request_log])
        met = {
            "failed_requests": stats.get("failed_requests", 0),
            "retries": stats.get("retries", 0),
            "p95_latency_ticks": float(np.percentile(lat, 95)),
            "total_ticks": float(eng.ticks),
            "goodput_tokens_per_s": done_tokens / wall_s,   # wall: not gated
        }
        if not fail_steps:      # clean trace: the policy must be invisible
            assert met["retries"] == 0 and met["failed_requests"] == 0, met
        rows.append((
            f"serving_fault_sweep/continuous@{label}", wall_s * 1e6,
            f"done={lat.size}/{n_req} retries={met['retries']} "
            f"failed={met['failed_requests']} "
            f"p95={met['p95_latency_ticks']:.0f}t "
            f"goodput={met['goodput_tokens_per_s']:.1f}tok/s", met))
    return rows


def run(quick: bool = False) -> List[BenchRow]:
    return (sac_rows(quick) + alexnet_sweep() + sharded_sweep()
            + decode_sweep(quick) + sharded_decode_sweep(quick)
            + moe_decode_sweep(quick)
            + serving_rows(quick) + serving_load_sweep(quick)
            + serving_fault_sweep(quick))


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="CI smoke mode: small shapes, fewer bit widths")
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also write rows (with metrics) as JSON to PATH")
    args = parser.parse_args(argv)
    rows = run(quick=args.quick)
    from benchmarks.common import print_rows
    print_rows([(name, us, derived) for name, us, derived, _ in rows])
    if args.json:
        payload = [{"name": name, "us_per_call": us, "derived": derived,
                    "metrics": metrics}
                   for name, us, derived, metrics in rows]
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)


if __name__ == "__main__":
    main()
