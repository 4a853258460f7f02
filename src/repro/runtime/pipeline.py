"""GPipe-style pipeline parallelism over the "pod" axis (shard_map).

At two pods the cross-pod (DCN-class) link is the weakest; pipelining layers
across pods converts per-layer FSDP gathers over that link into one
activation hand-off per microbatch per stage boundary — the canonical
PP trade (bandwidth per step: activations*num_microbatches vs params*2).

Implementation: the classic collective_permute schedule.  Each pod owns
``num_layers / num_stages`` layers (stacked param leading dim is split).
Microbatches stream through: at tick t, stage s runs microbatch (t - s) if
0 <= t - s < M, then the activations rotate one stage forward.  Bubble
fraction = (S-1)/(M+S-1).

This is an optional execution mode (``--pipeline`` in launch.train and the
pp dry-run in EXPERIMENTS.md §Dry-run): DP/TP (FSDP+TP) remains the default.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_apply(
    layer_fn: Callable[[PyTree, jax.Array], jax.Array],
    stacked_params: PyTree,
    x: jax.Array,                    # [M, mb, S, D] microbatched activations
    mesh: Mesh,
    stage_axis: str = "pod",
) -> jax.Array:
    """Run ``layer_fn`` over stacked layers, pipelined across ``stage_axis``.

    stacked_params leaves: [L, ...] with L % num_stages == 0.
    x: [M, mb, ...] microbatches (M >= num_stages for reasonable bubbles).
    Returns activations in the same [M, mb, ...] layout.
    """
    num_stages = mesh.shape[stage_axis]
    m = x.shape[0]

    def stage_fn(params_local, x_local):
        # params_local: [L/S, ...]; x_local: full [M, mb, ...] (replicated on
        # the stage axis — each stage computes its slice of the schedule)
        stage = jax.lax.axis_index(stage_axis)

        def run_stage(xmb):
            def body(h, p_l):
                return layer_fn(p_l, h), None
            h, _ = jax.lax.scan(body, xmb, params_local)
            return h

        def tick(carry, t):
            buf = carry                       # [M, mb, ...] rolling buffer
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < m)
            idx = jnp.clip(mb_idx, 0, m - 1)
            xmb = jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
            ymb = jax.lax.cond(active, run_stage, lambda z: z, xmb)
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, ymb, idx, 0)
            # hand the buffer one stage forward; the last stage feeds results
            # back to stage 0's buffer slot (ring), which is correct because
            # each microbatch is only re-read after all stages touched it.
            buf = jax.lax.ppermute(
                buf, stage_axis,
                [(i, (i + 1) % num_stages) for i in range(num_stages)])
            return buf, None

        total_ticks = m + num_stages - 1
        buf, _ = jax.lax.scan(tick, x_local, jnp.arange(total_ticks))
        # Each physical ring buffer carries exactly the microbatches whose
        # phase matches its starting stage (slot m rides the buffer that
        # meets stage s at tick m+s).  The stage holding buffer j at the end
        # owns the finished slots with m % S == (total_ticks - stage) % S;
        # mask the rest and combine across stages with one psum.
        own = (jnp.arange(m) % num_stages) == ((total_ticks - stage)
                                               % num_stages)
        own = own.reshape((m,) + (1,) * (buf.ndim - 1))
        return jax.lax.psum(jnp.where(own, buf, 0), stage_axis)

    in_specs = (jax.tree.map(lambda _: P(stage_axis), stacked_params),
                P())
    return jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(stacked_params, x)
