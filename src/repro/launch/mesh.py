"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

Production topology (TPU v5e):
  single-pod : 16 x 16  = 256 chips, axes ("data", "model")
  multi-pod  : 2 x 16 x 16 = 512 chips, axes ("pod", "data", "model")
The "pod" axis carries data parallelism across pods (gradient all-reduce
over DCN) and optionally pipeline stages (runtime.pipeline).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    """Auto axis types: the sharding rules constrain with
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    (Explicit) axes refuse."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def make_host_mesh():
    """Whatever devices exist right now, as a 1-D 'data' mesh (examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), _auto(1))


def make_model_mesh(num_devices: int | None = None):
    """The first ``num_devices`` devices as a 1-D "model" mesh.

    The sharded kneaded CNN serving mesh (docs/DESIGN.md §5): out-channel
    (N) shards of every layer's compacted schedule live one per device on
    this axis.  ``None`` takes every visible device; on CPU force more with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    import numpy as np
    devs = jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(f"requested {num_devices} devices, "
                             f"only {len(devs)} visible")
        devs = devs[:num_devices]
    return jax.sharding.Mesh(np.asarray(devs), ("model",))


def make_serving_mesh(model_shards: int = 1, *, expert_shards: int = 1):
    """The kneaded serving mesh (docs/DESIGN.md §8, §13).

    ``expert_shards <= 1`` keeps the historical 1-D ("model",) mesh —
    N-shards of every compacted schedule, one per device.  With
    ``expert_shards > 1`` the mesh becomes 2-D ("expert", "model") over the
    first ``expert_shards * model_shards`` devices: kneaded MoE expert
    banks shard whole experts on "expert" while the dense projections'
    N-shards stay on "model" (each axis replicates over the other).
    """
    if expert_shards <= 1:
        return make_model_mesh(model_shards)
    import numpy as np
    need = expert_shards * model_shards
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(f"requested {expert_shards}x{model_shards} devices, "
                         f"only {len(devs)} visible")
    arr = np.asarray(devs[:need]).reshape(expert_shards, model_shards)
    return jax.sharding.Mesh(arr, ("expert", "model"))


# v5e hardware constants used by the dry-run cost terms (launch/dryrun.py).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
