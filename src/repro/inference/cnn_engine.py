"""CNN serving engine — the paper's own workload, served fully kneaded.

``CNNServingEngine`` is the CNN sibling of the LM ``ServingEngine``: it takes
a trained float checkpoint of an AlexNet/VGG-16/NiN-style model, converts
every conv/fc layer to the kneaded bit-plane format (conv layers via their
im2col [C*kh*kw, out_ch] matrices, zero-padded to tile alignment), and runs
the whole forward pass through the selected SAC execution path:

  impl="float"   — original float weights, plain f32 matmuls (the baseline)
  impl="int"     — integer-code matmul, scale in the epilogue (production CPU)
  impl="planes"  — paper-faithful per-plane SAC (the kernel's semantic oracle)
  impl="pallas"  — the schedule-compacted Pallas kernel (interpret on CPU,
                   compiled on TPU): each conv layer is ONE pallas_call whose
                   grid streams all activation rows and executes only the
                   work items of the layer's KneadedSchedule — built once
                   here at engine init (inside knead) and stored on each
                   KneadedWeight

"planes" and "pallas" are bit-exact against each other; all kneaded paths
match the float model within the quantization error bound.

Scaling (docs/DESIGN.md §5):

* ``shards=N`` partitions every layer's KneadedSchedule along its
  out-channel dimension over an N-device "model" mesh — the Pallas kernel
  then launches once per device under ``jax.shard_map``, each device
  executing only *its shard's* occupancy nonzeros (sharded == single-device
  bit-exact; ``layer_report`` adds per-shard work + imbalance columns).
* ``submit()``/``drain()`` is the batched request front end: single-image
  requests queue and drain in padding-bucket micro-batches — the stacked
  batch pads up to a fixed bucket size so the jitted forward compiles once
  per bucket while the kernel grid's M dimension absorbs the extra rows —
  with per-request latency recorded (``latency_stats``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.kneading import (KneadedWeight, ShardedKneadedWeight,
                                 kneaded_codes, kneading_ratio)
from repro.core.quantization import quantize
from repro.core.sac import SAC_IMPLS
from repro.inference.frontend import (RequestFrontEnd, RequestHandle,
                                      validate_buckets)
from repro.inference.resilience import ServingFaultPolicy
from repro.models import cnn
from repro.runtime.spans import span

PyTree = Any


@dataclasses.dataclass(frozen=True)
class CNNServingConfig:
    impl: str = "int"          # "float" | "int" | "planes" | "pallas"
    bits: int = 8              # kneaded fixed-point width
    ks: int = 256              # kneading stride == kernel K tile
    n_block: int = 128         # kernel N tile (occupancy/schedule granularity)
    jit: bool = True
    # Retain the float checkpoint after kneading so layer_report() can
    # derive cycle statistics cheaply.  Set False for long-lived serving
    # processes that only need the forward pass — the kneaded params alone
    # then realize the advertised ~bits/16 memory footprint in-process, and
    # layer_report() falls back to reconstructing codes from the packed
    # planes (exact, just slower).
    keep_float_params: bool = True
    # Shard every layer's kneaded weight + schedule along N over this many
    # mesh devices (0/1 = single device).  Requires impl="pallas" — the
    # sharded work lists are a kernel-path artifact.
    shards: int = 0
    # tile→shard partitioning of the sharded schedules: "contiguous" slabs
    # or occupancy-"balanced" LPT packing (docs/DESIGN.md §11)
    shard_partition: str = "contiguous"
    mesh_axis: str = "model"
    # Micro-batch padding buckets for submit()/drain(), ascending.  A drain
    # chunk pads to the smallest bucket that fits so the jitted forward
    # compiles once per bucket instead of once per request count.
    buckets: Tuple[int, ...] = (1, 2, 4, 8)
    # Per-request log entries retained for latency_stats() — a sliding
    # window, so a long-lived serving process doesn't grow without bound.
    stats_window: int = 4096
    # Fault handling (docs/DESIGN.md §10).  The CNN path is a single
    # forward per micro-batch — no retries/slots to recover — so only the
    # policy's NaN/Inf logit guard applies here: a non-finite logits row
    # FAILs just that request instead of returning garbage for the batch.
    fault_policy: Optional[ServingFaultPolicy] = None


class CNNServingEngine(RequestFrontEnd):
    """Classify images through a fully-kneaded CNN forward pass."""

    def __init__(self, cfg: cnn.CNNConfig, params: PyTree,
                 scfg: CNNServingConfig = CNNServingConfig()):
        if scfg.impl not in SAC_IMPLS:
            raise ValueError(f"impl must be one of {SAC_IMPLS}, "
                             f"got {scfg.impl!r}")
        if scfg.shards > 1 and scfg.impl != "pallas":
            raise ValueError("sharded serving runs the Pallas kernel; "
                             f"impl={scfg.impl!r} is single-device only")
        validate_buckets(scfg.buckets)
        self.cfg, self.scfg = cfg, scfg
        self.mesh = None
        if scfg.impl == "float":
            self.params = params
            self.float_params = params
        else:
            self.params = cnn.knead_params(params, bits=scfg.bits,
                                           ks=scfg.ks, n_block=scfg.n_block)
            self.float_params = params if scfg.keep_float_params else None
            if scfg.shards > 1:
                from repro.launch.mesh import make_model_mesh
                from repro.runtime.sharding import kneaded_shardings
                self.mesh = make_model_mesh(scfg.shards)
                self.params = cnn.shard_kneaded_params(
                    self.params, self.mesh, axis=scfg.mesh_axis,
                    partition=scfg.shard_partition)
                self.params = jax.device_put(
                    self.params, kneaded_shardings(self.params, self.mesh,
                                                   axis=scfg.mesh_axis))

        def fwd(p, x):
            return cnn.apply(p, x, cfg, impl=scfg.impl, mesh=self.mesh,
                             shard_axis=scfg.mesh_axis)

        self._fwd = jax.jit(fwd) if scfg.jit else fwd
        self._init_front_end(scfg.stats_window)

    def logits(self, x: jax.Array) -> jax.Array:
        """x [B, H, W, C] -> logits [B, num_classes]."""
        return self._fwd(self.params, x)

    def classify(self, x: jax.Array) -> jax.Array:
        """x [B, H, W, C] -> predicted class ids [B] int32."""
        return jnp.argmax(self.logits(x), axis=-1).astype(jnp.int32)

    # ------------------------------------------------- batched request front end

    def submit(self, x: jax.Array) -> "RequestHandle":
        """Queue one single-image request [H, W, C].

        Returns a :class:`~repro.inference.frontend.RequestHandle` (an
        int-compatible request id with ``result()``/``stream()``/
        ``cancel()``).  Requests accumulate until :meth:`drain` runs them
        in padding-bucket micro-batches; per-request latency is measured
        from this call to the completion of the micro-batch that served
        it.  The image shape is validated here, against the model config,
        so a bad request fails at submit with a clear error rather than
        as a shape mismatch deep inside the jitted forward.
        """
        if x.ndim != 3:
            raise ValueError(f"submit takes one image [H, W, C], "
                             f"got shape {tuple(x.shape)}")
        want = (self.cfg.image_size, self.cfg.image_size,
                self.cfg.in_channels)
        if tuple(x.shape) != want:
            raise ValueError(f"image shape {tuple(x.shape)} does not match "
                             f"the model's input {want} "
                             f"(image_size={self.cfg.image_size}, "
                             f"in_channels={self.cfg.in_channels})")
        return self._new_request(x)

    def drain(self) -> Dict[int, jax.Array]:
        """Serve every pending request; returns {request_id: logits}.

        Pending requests split into chunks of at most ``max(buckets)``
        images; each chunk stacks on the batch axis and zero-pads up to the
        smallest bucket that fits (the padded rows ride the kernel grid's M
        dimension and are sliced off), so the jitted forward sees one shape
        per bucket — no per-request-count retraces.
        """
        buckets = self.scfg.buckets
        cap = buckets[-1]
        results: Dict[int, jax.Array] = {}
        while self._pending:
            chunk, self._pending = self._pending[:cap], self._pending[cap:]
            b = len(chunk)
            bucket = next(bk for bk in buckets if bk >= b)
            start = time.perf_counter()
            start_tick = self.ticks
            with span("serve.batch", n=b, bucket=bucket):
                xb = jnp.stack([r.payload for r in chunk])
                if bucket > b:
                    xb = jnp.pad(xb, ((0, bucket - b),) + ((0, 0),) * 3)
            self.ticks += 1                     # one jitted forward launch
            with span("serve.forward"):
                out = self.logits(xb)
            with span("serve.forward_sync"):
                out = jax.block_until_ready(out)
            with span("serve.finish"):
                self._finish(chunk, out[:b], bucket, start, start_tick,
                             results)
        return results

    def _finish(self, chunk, out: jax.Array, bucket: int, start: float,
                start_tick: int, results: Dict[int, jax.Array]) -> None:
        """Per-request bookkeeping of one served chunk."""
        from repro.inference import frontend as fe
        b = len(chunk)
        done = time.perf_counter()
        pol = self.scfg.fault_policy
        bad_rows = set()
        if pol is not None and pol.nan_guard:
            import numpy as np
            finite = np.isfinite(np.asarray(out).astype(np.float32))
            bad_rows = {i for i in range(b) if not finite[i].all()}
        for i, req in enumerate(chunk):
            if i in bad_rows:
                req.state = fe.FAILED
                req.error = "non-finite logits"
                req.finish_t = done
                req.finish_tick = self.ticks
                self._fault_event("nan_quarantined", id=req.id)
                self._fault_event("failed_requests", id=req.id,
                                  reason=req.error)
                continue
            req.state = fe.DONE
            req.result = out[i]
            req.admit_t, req.finish_t = start, done
            req.admit_tick, req.finish_tick = start_tick, self.ticks
            results[req.id] = req.result
            self._log_request(
                id=req.id,
                latency_ms=(done - req.submit_t) * 1e3,
                queue_wait_ms=(start - req.submit_t) * 1e3,
                decode_ms=(done - start) * 1e3,
                latency_ticks=self.ticks - req.submit_tick,
                bucket=bucket,
                batch_fill=b / bucket,
            )

    # ------------------------------------------------------------- reporting

    def serving_bytes(self) -> int:
        """HBM bytes of the serving params (kneaded packed or bf16 floats)."""
        total = 0
        kinds = (KneadedWeight, ShardedKneadedWeight)
        for leaf in jax.tree.leaves(self.params,
                                    is_leaf=lambda x: isinstance(x, kinds)):
            if isinstance(leaf, kinds):
                total += leaf.packed_bytes()
            else:
                total += leaf.size * 2          # floats serve as bf16
        return total

    def _layer_codes(self, name: str, kw) -> Optional[jax.Array]:
        """Integer codes of one layer for the cycle model.

        From the retained float checkpoint when present (cheap re-quantize);
        otherwise reconstructed exactly from the packed planes — identical
        on the logical region, since alignment padding quantizes to all-zero
        codes without disturbing the per-channel scales.  Sharded engines
        without the float checkpoint skip cycle stats (the planes live
        device-sharded; gathering them to count bits defeats the point of
        dropping the checkpoint).
        """
        if self.float_params is not None:
            return quantize(self.float_params[name]["w"], bits=kw.bits,
                            axis=-1).q
        if isinstance(kw, KneadedWeight):
            return kneaded_codes(kw)[:kw.logical_k, :kw.logical_n]
        return None

    def layer_report(self, cycle_ks: int = 16) -> List[Dict[str, Any]]:
        """Per-layer kneaded footprint + cycle stats (Fig 9/11 companions).

        ``cycle_ks`` is the *hardware* kneading stride of the cycle model
        (the paper sweeps 10..32) — independent of the storage-format stride
        ``scfg.ks`` that sizes the kernel's K tiles.  Codes come from the
        float checkpoint when retained, else from the packed planes (see
        :meth:`_layer_codes`); ``cycle_ratio`` is None when neither is
        available.  Sharded engines add ``shard_work`` (executed MXU passes
        per device) and ``shard_imbalance`` (max/mean) columns.
        """
        if self.scfg.impl == "float":
            raise ValueError("layer_report needs kneaded params "
                             "(impl != 'float')")
        rows = []
        for name, p in self.params.items():
            kw = p["w"]
            row = {
                "layer": name,
                "shape": (kw.logical_k, kw.logical_n),
                "bytes_vs_bf16": kw.packed_bytes() / kw.dense_bf16_bytes(),
                "cycle_ratio": None,
            }
            if isinstance(kw, ShardedKneadedWeight):
                imb = kw.imbalance()
                row.update({
                    "executed_tile_dots": kw.total_work,
                    "dense_tile_dots": kw.dense_work(),
                    "shard_work": imb["shard_work"],
                    "shard_imbalance": imb["imbalance"],
                })
            else:
                sched = kw.schedule
                # compacted-schedule accounting: MXU passes the pallas path
                # executes per M-step vs what the dense grid would have run
                row.update({
                    "executed_tile_dots": sched.total_work,
                    "dense_tile_dots": sched.dense_work(kw.bits),
                })
            q = self._layer_codes(name, kw)
            if q is not None:
                k = (q.shape[0] // cycle_ks) * cycle_ks
                row["cycle_ratio"] = float(
                    kneading_ratio(q[:k], kw.bits, cycle_ks))
            rows.append(row)
        return rows
