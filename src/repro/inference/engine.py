"""Batched serving engine with the Tetris kneaded-weight path.

``ServingEngine`` owns: prefill -> padded KV cache -> batched greedy/sampled
decode, plus the ``submit()``/``drain()`` request front end (padding-bucket
micro-batches, per-request latency).  ``knead_params`` converts a trained
float checkpoint into a serving representation — either the quantized-matmul
form (QuantizedTensor int8 / PackedInt4: integer codes with a single
epilogue scale) or, with ``kneaded=True``, the full kneaded bit-plane form
of docs/DESIGN.md §7: every ``_KNEADABLE`` projection becomes a
:class:`KneadedWeight` with a compacted
:class:`~repro.core.schedule.KneadedSchedule`, stacked [L, K, N] scan-layer
weights kneaded per layer with a leading schedule axis
(:func:`repro.core.kneading.knead_stacked`), so attention and MLP
projections dispatch through ``sac_matmul`` — and with ``impl="pallas"``
through the schedule-walking SAC kernel's decode-GEMV fast path.

``shards=N`` (docs/DESIGN.md §8) additionally partitions every kneaded
projection's compacted work lists along the out-channel dim over an
N-device "model" mesh: stacked scan-layer weights become
:class:`~repro.core.schedule.ShardedStackedKneadedWeight` (per-layer
per-shard work lists, scan-sliceable), [K, N] leaves become
:class:`~repro.core.schedule.ShardedKneadedWeight`, and every sharded
matmul launches one Pallas call per device under ``jax.shard_map`` — the
same engine API, now tensor-parallel, bit-exact against one device.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.inference.frontend import (RequestFrontEnd, RequestHandle,
                                      validate_buckets)
from repro.inference.resilience import ServingFaultPolicy, verify_kneaded_tree
from repro.inference.scheduler import ContinuousScheduler
from repro.core.kneading import (KNEADABLE_NAMES, KneadedWeight,
                                 ShardedKneadedWeight,
                                 knead_padded, knead_stacked,
                                 shard_schedule, shard_stacked_schedule)
from repro.core.quantization import quantize
from repro.core.sac import SAC_IMPLS
from repro.kernels.kneaded_gemm.ref import pack_int4
from repro.models.layers import PackedInt4
from repro.models.lm import LanguageModel

PyTree = Any

_log = logging.getLogger(__name__)

# single shared definition (repro.core.kneading) — launch/specs.py reads the
# same tuple, so the two serving paths can't drift on what gets kneaded
_KNEADABLE = KNEADABLE_NAMES


def knead_params(params: PyTree, bits: int = 8, min_dim: int = 128,
                 *, kneaded: bool = False, ks: int = 256,
                 n_block: int = 128, shards: int = 0,
                 shard_partition: str = "contiguous") -> PyTree:
    """Convert every kneadable projection leaf to its serving form.

    Default (``kneaded=False``): quantize to intN codes — bits=8 ->
    QuantizedTensor; bits=4 -> PackedInt4 (nibble-packed along K).  Stacked
    [L, K, N] leaves are quantized per (layer, out-channel).

    ``kneaded=True``: the full bit-plane serving form — [K, N] leaves via
    :func:`~repro.core.kneading.knead_padded` (arbitrary dims zero-padded to
    tile alignment, exactly), leaves with any leading stack axes via
    :func:`~repro.core.kneading.knead_stacked` (per-slice schedules with the
    stack axes in front, sliced out by the model's layer scans): [L, K, N]
    scan-layer weights AND [L, E, K, N] MoE expert banks (docs/DESIGN.md
    §13 — each expert kneaded independently, served per-expert through the
    SAC decode-GEMV path).  ``min_dim`` gates tiny projections either way;
    kneadable leaves that stay un-kneaded are named in a one-line warning
    instead of silently serving their float/quant form.

    ``shards=N`` (with ``kneaded=True``) then partitions every kneaded
    leaf's work lists along N — stacked leaves per layer
    (:func:`~repro.core.kneading.shard_stacked_schedule`), [K, N] leaves via
    :func:`~repro.core.kneading.shard_schedule` — producing the mesh-ready
    sharded serving tree of docs/DESIGN.md §8 (a plain int here: placement
    happens at ``device_put`` time via
    ``runtime.sharding.kneaded_shardings``).  Expert banks are NOT
    N-sharded: they place whole experts on the "expert" mesh axis
    (``ServingConfig.expert_shards``).
    """
    if shards > 1 and not kneaded:
        raise ValueError("shards applies to the kneaded serving form only "
                         "(pass kneaded=True)")
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    unkneaded = []
    for path, leaf in flat:
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        name = keys[-1] if keys else ""
        ok = (name in _KNEADABLE and hasattr(leaf, "ndim") and leaf.ndim >= 2
              and leaf.shape[-1] >= min_dim and leaf.shape[-2] >= min_dim
              and leaf.shape[-2] % 2 == 0)
        if kneaded:
            ok = (name in _KNEADABLE and hasattr(leaf, "ndim")
                  and leaf.ndim >= 2
                  and leaf.shape[-1] >= min_dim
                  and leaf.shape[-2] >= min_dim)
            if not ok and name in _KNEADABLE and hasattr(leaf, "ndim"):
                unkneaded.append("/".join(keys) +
                                 f" {tuple(leaf.shape)}")
        if not ok:
            out.append(leaf)
            continue
        if kneaded:
            if leaf.ndim == 2:
                kw = knead_padded(leaf, bits=bits, ks=ks, n_block=n_block)
                if shards > 1:
                    kw = shard_schedule(kw, shards,
                                        partition=shard_partition)
            else:
                kw = knead_stacked(leaf, bits=bits, ks=ks, n_block=n_block)
                if shards > 1 and leaf.ndim == 3:
                    # expert banks (ndim >= 4) are never N-sharded: whole
                    # experts place on the "expert" mesh axis instead
                    kw = shard_stacked_schedule(kw, shards,
                                                partition=shard_partition)
            out.append(kw)
            continue
        qt = quantize(leaf, bits=bits, axis=-1, reduce_axes=(-2,))
        scale = qt.scale  # [..., 1, N] per (stack..., out-channel)
        if bits == 4:
            k = leaf.shape[-2]
            q2 = qt.q.reshape((-1,) + leaf.shape[-2:])
            packed = jnp.stack([pack_int4(q) for q in q2])
            packed = packed.reshape(leaf.shape[:-2] + (k // 2, leaf.shape[-1]))
            out.append(PackedInt4(packed=packed, scale=scale, k=k))
        else:
            out.append(dataclasses.replace(qt, scale=scale))
    if unkneaded:
        _log.warning("serving un-kneaded (below min_dim=%d): %s",
                     min_dim, ", ".join(unkneaded))
    return jax.tree_util.tree_unflatten(treedef, out)


def serving_bytes(params: PyTree) -> int:
    """HBM bytes of a serving param tree (bf16 floats, intN codes, or the
    packed kneaded format incl. schedule metadata; sharded leaves count
    across all shards)."""
    total = 0
    kinds = (KneadedWeight, ShardedKneadedWeight)
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, kinds)):
        if isinstance(leaf, kinds):
            total += leaf.packed_bytes()
        elif hasattr(leaf, "dtype") and hasattr(leaf, "size"):
            itemsize = jnp.dtype(leaf.dtype).itemsize
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                itemsize = 2     # floats serve as bf16
            total += leaf.size * itemsize
    return total


@dataclasses.dataclass
class ServingConfig:
    max_len: int = 512
    temperature: float = 0.0      # 0 => greedy
    quant_bits: int = 0           # 0 => bf16, else 8 or 4
    # Serving execution path:
    #   "quant"  — the quantized-matmul form above (quant_bits selects width)
    #   "float"  — original float params, plain bf16 matmuls
    #   "int" | "planes" | "pallas" — knead every projection to the bit-plane
    #            form and run SAC through that path ("pallas" = the
    #            schedule-compacted kernel with the decode-GEMV fast path;
    #            "planes" = its bit-exact oracle; "int" = one integer-code
    #            matmul, the fast CPU reference).  Kneading width is
    #            quant_bits (default 8 when 0).
    impl: str = "quant"
    knead_ks: int = 256           # kneading stride == kernel K tile
    knead_n_block: int = 128      # kernel N tile / schedule granularity
    knead_min_dim: int = 128      # skip projections smaller than this
    # Shard every kneaded projection's compacted schedule along its
    # out-channel dim over this many "model"-mesh devices (0/1 = single
    # device).  Requires impl="pallas" — sharded work lists are a kernel-
    # path artifact (docs/DESIGN.md §8).
    shards: int = 0
    # Tile→shard partitioning of sharded schedules (docs/DESIGN.md §11):
    #   "contiguous" — each shard takes a contiguous N-tile slab
    #   "balanced"   — LPT bin-packing on static per-tile occupancy, with
    #                  a recorded permutation gathered back after the
    #                  per-device kernels (bit-exact either way)
    shard_partition: str = "contiguous"
    # Expert parallelism for kneaded MoE banks (docs/DESIGN.md §13): place
    # whole experts of every [L, E, K, N] kneaded bank on a dedicated
    # "expert" mesh axis (0/1 = all experts local).  Orthogonal to
    # ``shards`` — the mesh becomes ("expert", "model"), expert banks
    # shard on "expert", the dense projections' N-shards stay on "model".
    # Requires a kneaded impl ("int"/"planes"/"pallas") and
    # num_experts % expert_shards == 0; bit-exact vs all-experts-local
    # through the combine psum.
    expert_shards: int = 0
    mesh_axis: str = "model"
    # submit()/drain() batching: micro-batch padding buckets (ascending)
    # and the sliding per-request latency log window.
    buckets: Tuple[int, ...] = (1, 2, 4, 8)
    stats_window: int = 4096
    # Request scheduler (docs/DESIGN.md §9):
    #   "batch"      — submit() queues, drain() serves padding-bucket
    #                  micro-batches to completion (wave-synchronous).
    #   "continuous" — step-level slot scheduler: prompts admit into free
    #                  slots each step, finished requests retire (and free
    #                  their KV blocks) immediately; handles stream tokens
    #                  as they decode.  drain() remains a thin wrapper.
    scheduler: str = "batch"
    max_inflight: int = 8         # continuous: in-flight slot capacity
    # continuous KV pool: block granularity in tokens (0 = dense rows at
    # max_len) and total pool budget in tokens (0 = slots * max_len)
    kv_block: int = 32
    kv_pool_tokens: int = 0
    # continuous: cap on admitted prompt tokens per scheduler step (0 =
    # uncapped) — bounds how much prefill work interleaves one decode step
    prefill_chunk: int = 0
    # Fault handling (docs/DESIGN.md §10): bounded per-request retries,
    # NaN-logit quarantine, decode-step watchdog, impl-demotion ladder,
    # and knead-time checksum verification.  None (default) keeps the
    # pre-resilience behavior exactly — no guards, exceptions propagate.
    fault_policy: Optional[ServingFaultPolicy] = None


class ServingEngine(RequestFrontEnd):
    def __init__(self, cfg: ModelConfig, params: PyTree,
                 scfg: ServingConfig = ServingConfig()):
        if scfg.impl not in ("quant",) + SAC_IMPLS:
            raise ValueError(f"impl must be 'quant' or one of {SAC_IMPLS}, "
                             f"got {scfg.impl!r}")
        if scfg.shards > 1 and scfg.impl != "pallas":
            raise ValueError("sharded serving runs the Pallas kernel; "
                             f"impl={scfg.impl!r} is single-device only")
        if scfg.expert_shards > 1:
            if scfg.impl not in SAC_IMPLS:
                raise ValueError(
                    "expert_shards places kneaded expert banks on the "
                    f"'expert' mesh axis; impl={scfg.impl!r} does not "
                    f"knead (use one of {SAC_IMPLS})")
            if not cfg.num_experts:
                raise ValueError("expert_shards requires an MoE config "
                                 f"(num_experts=0 in {cfg.name!r})")
            if cfg.num_experts % scfg.expert_shards:
                raise ValueError(
                    f"num_experts={cfg.num_experts} not divisible by "
                    f"expert_shards={scfg.expert_shards}")
        if scfg.scheduler not in ("batch", "continuous"):
            raise ValueError(f"scheduler must be 'batch' or 'continuous', "
                             f"got {scfg.scheduler!r}")
        if scfg.scheduler == "continuous":
            if cfg.family in ("vlm", "encdec"):
                raise ValueError(
                    f"continuous scheduler serves token-prompt families "
                    f"only; {cfg.family!r} prefill needs side inputs "
                    f"(frames/image embeddings) — use scheduler='batch'")
            if scfg.max_inflight < 1:
                raise ValueError("max_inflight must be >= 1")
        validate_buckets(scfg.buckets)
        self.scfg = scfg
        self.mesh = None
        # fault policy keeps the float checkpoint around: the integrity
        # repair path re-kneads corrupt leaves from it (a tree of
        # references, not a copy — the caller holds these arrays anyway)
        self._float_params = params if scfg.fault_policy is not None \
            else None
        integrity_report = []
        if scfg.impl in ("quant", "float"):
            self.cfg = cfg
            self.params = (knead_params(params, bits=scfg.quant_bits,
                                        min_dim=scfg.knead_min_dim)
                           if scfg.impl == "quant" and scfg.quant_bits
                           else params)
        else:
            # kneaded serving: the model dispatches every KneadedWeight
            # matmul through the configured SAC path
            self.cfg = dataclasses.replace(cfg, impl=scfg.impl)
            self.params = knead_params(
                params, bits=scfg.quant_bits or 8,
                min_dim=scfg.knead_min_dim, kneaded=True,
                ks=scfg.knead_ks, n_block=scfg.knead_n_block,
                shards=scfg.shards,
                shard_partition=scfg.shard_partition)
            if scfg.fault_policy is not None and \
                    scfg.fault_policy.verify_weights:
                # before device placement: a repaired leaf re-kneads on
                # host, so sharded trees verify pre-device_put
                self.params, integrity_report = verify_kneaded_tree(
                    self.params, self._float_params, shards=scfg.shards)
            if scfg.shards > 1 or scfg.expert_shards > 1:
                from repro.launch.mesh import make_serving_mesh
                from repro.runtime.sharding import kneaded_shardings
                self.mesh = make_serving_mesh(
                    max(scfg.shards, 1),
                    expert_shards=max(scfg.expert_shards, 1))
                self.params = jax.device_put(
                    self.params, kneaded_shardings(self.params, self.mesh,
                                                   axis=scfg.mesh_axis))
        cfg = self.cfg
        self.model = LanguageModel(cfg)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(3,))
        self._init_front_end(scfg.stats_window)
        for row in integrity_report:
            self._fault_event("integrity_repairs", **row)
        self._scheduler = (ContinuousScheduler(self)
                           if scfg.scheduler == "continuous" else None)

    # ------------------------------------------- resilience (§10; policy)

    def _demote_impl(self, reason: str) -> bool:
        """Graceful degradation: move one rung down the fault policy's
        ``fallback_impls`` ladder and rebuild the jitted model functions.

        Possible because every SAC impl dispatches per call on the same
        :class:`~repro.core.kneading.KneadedWeight` params
        (``matmul_any -> sac_matmul``) — no re-kneading, no new device
        placement, just a re-jit.  ``pallas -> planes`` preserves the
        bit-exactness guarantee (planes is the kernel's bitwise oracle);
        ``planes -> float`` trades exactness for availability and is why
        every demotion logs a ``degradations`` event.  Returns False —
        never raises — when no rung remains, the engine is not on a
        kneaded impl, or the engine is sharded (sharded work lists are a
        Pallas-kernel artifact; there is no weaker impl that can read
        them, docs/DESIGN.md §8).
        """
        pol = self.scfg.fault_policy
        cur = self.scfg.impl
        if pol is None or not pol.fallback_impls:
            return False
        if cur in ("quant", "float") or self.scfg.shards > 1:
            return False
        ladder = list(pol.fallback_impls)
        if cur in ladder:
            nxt = ladder[ladder.index(cur) + 1] \
                if ladder.index(cur) + 1 < len(ladder) else None
        else:
            nxt = ladder[0]       # e.g. pallas -> head of (planes, float)
        if nxt is None or nxt == cur or nxt not in SAC_IMPLS:
            return False
        self.scfg = dataclasses.replace(self.scfg, impl=nxt)
        self.cfg = dataclasses.replace(self.cfg, impl=nxt)
        self.model = LanguageModel(self.cfg)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(3,))
        self._fault_event("degradations", impl_from=cur, impl_to=nxt,
                          reason=reason)
        return True

    def verify_weights(self, repair: bool = True):
        """Sweep the serving params for corrupted kneaded leaves (bit
        flips in planes/signs/occupancy or the compacted schedule arrays,
        checked against knead-time CRCs).  With ``repair``, corrupt
        leaves are re-kneaded from the retained float checkpoint —
        deterministic, hence bit-identical to the never-corrupted leaf.
        Returns the corruption report (empty = intact); logs one
        ``integrity_repairs`` event per repaired leaf.
        """
        self.params, report = verify_kneaded_tree(
            self.params, self._float_params, shards=self.scfg.shards,
            repair=repair)
        for row in report:
            self._fault_event("integrity_repairs", **row)
        return report

    def expert_work_table(self) -> Dict[str, Any]:
        """Static per-(layer, expert) kneaded work tables, one [L, E] host
        numpy array per kneaded expert bank ({path: table}).

        The ``layer_shard_work`` analogue for expert parallelism
        (docs/DESIGN.md §13): entry [l, e] is how many (plane, K-tile,
        N-tile) work items expert e of layer l owns in the compacted
        schedule — the static side of the routing-load accounting
        (``latency_stats()``'s ``routed_tokens``/``capacity_dropped``
        counters are the dynamic side), and the input the ROADMAP
        work-stealing item needs.  Empty for non-MoE / un-kneaded engines.
        """
        tables: Dict[str, Any] = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.params, is_leaf=lambda x: isinstance(x, KneadedWeight))
        for path, leaf in flat:
            if isinstance(leaf, KneadedWeight) and leaf.planes.ndim >= 5:
                name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                                for k in path)
                tables[name] = leaf.work_table()
        return tables

    def _mesh_ctx(self):
        """Serving-mesh context the sharded kneaded matmuls dispatch under
        (a no-op for unsharded engines; installed around every model call so
        jit traces capture the mesh — docs/DESIGN.md §8)."""
        import contextlib

        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.runtime.sharding import serving_mesh
        return serving_mesh(self.mesh, self.scfg.mesh_axis)

    def _pad_cache(self, cache: PyTree, cur: int) -> PyTree:
        """Pad the prefill cache's sequence axes out to ``max_len``.

        Structure-aware, keyed on the cache dict the model families build
        (models/lm.py): self-attention KV stores ("k"/"v", seq axis at -3)
        zero-pad; their int8-quantization scales ("k_scale"/"v_scale", seq
        at -2) pad with 1.0.  Everything else — cross-attention KV (fixed
        encoder/image extent) and SSM/conv states ("conv"/"ssm"/"mlstm"/
        "slstm", no seq axis at all) — is returned untouched.  Keying on
        names rather than sniffing shapes matters: a hybrid SSM state
        [L, B, H, p, n] whose head count H happens to equal the prompt
        length used to match the old "-3 axis == prefill len" heuristic and
        got its *heads* padded to max_len, breaking every zamba2 decode
        (the ROADMAP's hybrid-decode bug; regression-tested in
        tests/test_lm_kneaded.py).
        """
        pad_to = self.scfg.max_len

        def pad_axis(x, axis, value=0.0):
            if x.shape[axis] != cur or pad_to == cur:
                return x
            pads = [(0, 0)] * x.ndim
            pads[axis] = (0, pad_to - cur)
            return jnp.pad(x, pads, constant_values=value)

        out = dict(cache)
        for key in ("k", "v"):
            if key in out:
                out[key] = pad_axis(out[key], -3)
        for key in ("k_scale", "v_scale"):
            if key in out:
                out[key] = pad_axis(out[key], -2, value=1.0)
        return out

    def generate(self, batch: Dict[str, jax.Array], num_tokens: int,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Prefill on batch["tokens"] then decode ``num_tokens`` greedily
        (or sampled at temperature>0).  Returns [B, num_tokens] int32."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        assert s + num_tokens <= self.scfg.max_len
        # virtual-launch clock: one prefill + num_tokens decode launches
        self.ticks += 1 + num_tokens
        with self._mesh_ctx():
            logits, cache = self._prefill(self.params, batch)
            cache = self._pad_cache(cache, s)
            outs = []
            key = key if key is not None else jax.random.PRNGKey(0)
            tok = self._select(logits, key)
            for i in range(num_tokens):
                outs.append(tok)
                pos = jnp.full((b,), s + i, jnp.int32)
                logits, cache = self._decode(self.params, tok[:, None], pos,
                                             cache)
                key, sub = jax.random.split(key)
                tok = self._select(logits, sub)
            return jnp.stack(outs, axis=1)

    def _select(self, logits: jax.Array, key) -> jax.Array:
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / self.scfg.temperature,
            axis=-1).astype(jnp.int32)

    # ------------------------------------------- batched request front end

    def submit(self, tokens: jax.Array, num_tokens: int = 16, *,
               priority: int = 0,
               deadline: Optional[float] = None) -> RequestHandle:
        """Queue one single-prompt generation request.

        ``tokens`` is a 1-D int32 prompt.  Returns a
        :class:`~repro.inference.frontend.RequestHandle` — it compares/
        hashes as the integer request id (so the classic
        ``results = drain(); results[rid]`` flow is unchanged) and adds
        ``result()`` (block for this request), ``stream()`` (per-token
        iterator), and ``cancel()``.  ``priority`` orders admission under
        the continuous scheduler (higher first; FIFO within a priority);
        ``deadline`` (seconds from now) expires the request if it is
        still queued when the scheduler next looks at it.
        """
        if getattr(tokens, "ndim", None) != 1:
            raise ValueError("submit takes one prompt [S], got shape "
                             f"{tuple(getattr(tokens, 'shape', ()))}")
        if tokens.shape[0] < 1:
            raise ValueError("prompt must contain at least one token")
        if num_tokens < 1:
            raise ValueError(f"num_tokens must be >= 1, got {num_tokens}")
        total = int(tokens.shape[0]) + num_tokens
        if total > self.scfg.max_len:
            raise ValueError(f"prompt {tokens.shape[0]} + {num_tokens} "
                             f"tokens exceeds max_len={self.scfg.max_len}")
        if self._scheduler is not None and not self._scheduler.pool.fits(
                total):
            raise ValueError(
                f"request needs {total} KV tokens "
                f"({self._scheduler.pool.blocks_needed(total)} blocks) but "
                f"the pool holds {self._scheduler.pool.total_blocks} blocks "
                f"of {self._scheduler.pool.block} "
                f"(kv_pool_tokens={self.scfg.kv_pool_tokens})")
        return self._new_request(tokens, num_tokens, priority=priority,
                                 deadline=deadline)

    def drain(self) -> Dict[int, jax.Array]:
        """Serve every pending request; returns {request_id: tokens [n_i]}.

        Under ``scheduler="continuous"`` this is a thin compatibility
        wrapper: it runs the step loop until the wave that was pending at
        call time retires (admission/retirement still happen per step
        inside).  The batch-synchronous path below groups pending
        requests by prompt length (one prefill shape per group —
        positions stay exact with no prompt padding), then splits into
        chunks of at most ``max(buckets)``; each chunk stacks on the
        batch axis and zero-pads up to the smallest bucket that fits, so
        the jitted prefill/decode compile once per (prompt-len, bucket)
        rather than once per request count — the padded rows ride the
        kernel grid's M dimension.  The chunk decodes jointly for the
        chunk-max token budget (requests with smaller budgets finish
        early and their rows ride along as padding) and each request
        keeps its first ``num_tokens``.
        """
        if self._scheduler is not None:
            return self._scheduler.drain()
        from repro.inference import frontend as fe
        buckets = self.scfg.buckets
        cap = buckets[-1]
        results: Dict[int, jax.Array] = {}
        by_len: Dict[int, List] = collections.defaultdict(list)
        for req in self._pending:
            by_len[req.prompt_len].append(req)
        self._pending = []
        for plen in sorted(by_len):
            queue = by_len[plen]
            while queue:
                chunk, queue = queue[:cap], queue[cap:]
                b = len(chunk)
                bucket = next(bk for bk in buckets if bk >= b)
                start = time.perf_counter()
                start_tick = self.ticks
                toks = jnp.stack([r.payload for r in chunk])
                if bucket > b:
                    toks = jnp.pad(toks, ((0, bucket - b), (0, 0)))
                budget = max(r.num_tokens for r in chunk)
                out = jax.block_until_ready(
                    self.generate({"tokens": toks}, budget))
                done = time.perf_counter()
                for i, req in enumerate(chunk):
                    req.state = fe.DONE
                    req.result = out[i, :req.num_tokens]
                    req.admit_t, req.finish_t = start, done
                    req.admit_tick = start_tick
                    req.finish_tick = self.ticks
                    results[req.id] = req.result
                    self._log_request(
                        id=req.id,
                        latency_ms=(done - req.submit_t) * 1e3,
                        queue_wait_ms=(start - req.submit_t) * 1e3,
                        decode_ms=(done - start) * 1e3,
                        latency_ticks=self.ticks - req.submit_tick,
                        queue_wait_ticks=start_tick - req.submit_tick,
                        bucket=bucket,
                        batch_fill=b / bucket,
                        prompt_len=plen,
                        decode_tokens=budget,
                    )
        return results

    # ---- RequestHandle backends (continuous mode steps the scheduler
    # just far enough; batch mode falls back to the mixin's drain-all)

    def _result(self, req):
        if self._scheduler is not None:
            self._scheduler.run_until(req)
            return self._finished_result(req)
        return super()._result(req)

    def _stream(self, req):
        if self._scheduler is not None:
            return self._scheduler.stream(req)
        return super()._stream(req)

    def _cancel(self, req) -> bool:
        if self._scheduler is not None:
            return self._scheduler.cancel(req)
        return super()._cancel(req)

    def scheduler_step(self) -> bool:
        """Advance the continuous scheduler by one step (admit -> decode
        -> retire).  Returns True while work remains.  Batch mode: error."""
        if self._scheduler is None:
            raise ValueError("scheduler_step() requires "
                             "ServingConfig(scheduler='continuous')")
        return self._scheduler.step()
