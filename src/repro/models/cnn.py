"""CNNs from the paper's own evaluation set (AlexNet / VGG-16 / NiN style).

Convolution is implemented as im2col -> matmul so every conv layer is a
[K = C*kh*kw, N = out_ch] weight *matrix* — exactly the form weight kneading
and SAC consume (the paper's accelerator likewise lowers conv to weight/
activation lanes).  These models drive the paper-reproduction benchmarks
(Table 1, Figs 2/8/9/10/11) and run fully kneaded on the serving path:
``knead_params`` converts every conv/fc kernel to :class:`KneadedWeight`
(conv via its im2col matrix, zero-padded to tile alignment) and ``apply``
takes an ``impl`` selector ("float" | "int" | "planes" | "pallas") that
routes every layer's matmul through the chosen SAC execution path.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kneading import (KneadedWeight, ShardedKneadedWeight,
                                 knead_padded, shard_schedule)
# the single conv-lowering definition, shared with sac_conv2d so float and
# kneaded convolutions see identical patch layouts
from repro.kernels.sac_matmul.ops import im2col as _im2col
from repro.models import layers as L

# spec entries: ("conv", out_ch, k, stride) | ("pool", k) | ("fc", out)
CNNSpec = Sequence[Tuple]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    spec: CNNSpec
    in_channels: int = 3
    image_size: int = 32       # scaled-down inputs for CPU feasibility
    num_classes: int = 100


ALEXNET = CNNConfig("alexnet", (
    ("conv", 64, 3, 1), ("pool", 2),
    ("conv", 192, 3, 1), ("pool", 2),
    ("conv", 384, 3, 1), ("conv", 256, 3, 1), ("conv", 256, 3, 1),
    ("pool", 2),
    ("fc", 1024), ("fc", 1024), ("fc", 100),
))

VGG16 = CNNConfig("vgg16", (
    ("conv", 64, 3, 1), ("conv", 64, 3, 1), ("pool", 2),
    ("conv", 128, 3, 1), ("conv", 128, 3, 1), ("pool", 2),
    ("conv", 256, 3, 1), ("conv", 256, 3, 1), ("conv", 256, 3, 1), ("pool", 2),
    ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("pool", 2),
    ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("pool", 2),
    ("fc", 1024), ("fc", 1024), ("fc", 100),
))

NIN = CNNConfig("nin", (
    ("conv", 192, 5, 1), ("conv", 160, 1, 1), ("conv", 96, 1, 1), ("pool", 2),
    ("conv", 192, 5, 1), ("conv", 192, 1, 1), ("conv", 192, 1, 1), ("pool", 2),
    ("conv", 192, 3, 1), ("conv", 192, 1, 1), ("conv", 100, 1, 1),
))

CNN_ZOO = {c.name: c for c in (ALEXNET, VGG16, NIN)}




def init(key, cfg: CNNConfig) -> Dict:
    params: Dict = {}
    c = cfg.in_channels
    size = cfg.image_size
    keys = jax.random.split(key, len(cfg.spec))
    flat = None
    for i, item in enumerate(cfg.spec):
        kind = item[0]
        if kind == "conv":
            _, out_c, k, stride = item
            params[f"conv{i}"] = {
                "w": L.dense_init(keys[i], c * k * k, out_c,
                                  scale=float(np.sqrt(2.0 / (c * k * k)))),
                "b": jnp.zeros((out_c,), jnp.float32),
            }
            c = out_c
            size //= stride
        elif kind == "pool":
            size //= item[1]
        elif kind == "fc":
            _, out = item
            d_in = flat if flat is not None else c * size * size
            params[f"fc{i}"] = {
                "w": L.dense_init(keys[i], d_in, out,
                                  scale=float(np.sqrt(2.0 / d_in))),
                "b": jnp.zeros((out,), jnp.float32),
            }
            flat = out
    return params


def apply(params: Dict, x: jax.Array, cfg: CNNConfig,
          collect_activations: bool = False, impl: str = "float",
          mesh=None, shard_axis: str = "model"):
    """x [B, H, W, C] -> logits [B, classes] (+ per-layer matmul inputs).

    ``impl`` selects the execution path for kneaded layers (see module
    docstring); "float" runs plain f32 matmuls on float weights.  Kneaded
    conv layers go through :func:`repro.kernels.sac_matmul.ops.sac_conv2d`
    — im2col + schedule-compacted SAC matmul, one ``pallas_call`` per layer
    with all activation rows streamed through the kernel grid's M dimension.
    ``ShardedKneadedWeight`` layers (see :func:`shard_kneaded_params`) run
    one kernel launch per ``mesh`` device over ``shard_axis``, each walking
    its own shard's compacted work list; ``mesh=None`` executes the shards
    serially (the single-device oracle).
    """
    acts: Dict[str, jax.Array] = {}
    flat = False
    for i, item in enumerate(cfg.spec):
        kind = item[0]
        if kind == "conv":
            _, out_c, k, stride = item
            p = params[f"conv{i}"]
            if isinstance(p["w"], (KneadedWeight, ShardedKneadedWeight)):
                from repro.kernels.sac_matmul.ops import sac_conv2d
                if collect_activations:
                    patches = _im2col(x, k, stride)
                    acts[f"conv{i}"] = patches.reshape(-1, patches.shape[-1])
                x = sac_conv2d(x, p["w"], ksize=k, stride=stride, bias=p["b"],
                               impl=impl, mesh=mesh, axis=shard_axis)
            else:
                patches = _im2col(x, k, stride)
                if collect_activations:
                    acts[f"conv{i}"] = patches.reshape(-1, patches.shape[-1])
                x = L.matmul_any(patches, p["w"], jnp.float32,
                                 impl=impl) + p["b"]
            x = jax.nn.relu(x)
        elif kind == "pool":
            k = item[1]
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, k, k, 1), "VALID")
        elif kind == "fc":
            if not flat:
                x = x.reshape(x.shape[0], -1)
                flat = True
            if collect_activations:
                acts[f"fc{i}"] = x
            p = params[f"fc{i}"]
            if isinstance(p["w"], ShardedKneadedWeight):
                from repro.kernels.sac_matmul.ops import sac_matmul_pallas_sharded
                out = sac_matmul_pallas_sharded(x, p["w"], mesh, shard_axis)
                x = out[:, :p["w"].logical_n] + p["b"]
            else:
                x = L.matmul_any(x, p["w"], jnp.float32, impl=impl) + p["b"]
            if i != len(cfg.spec) - 1:
                x = jax.nn.relu(x)
    if x.ndim == 4:                 # NiN: global average pooling head
        x = jnp.mean(x, axis=(1, 2))
    return (x, acts) if collect_activations else x


def knead_params(params: Dict, bits: int = 8, ks: int = 256,
                 n_block: int = 128) -> Dict:
    """Convert every conv/fc kernel of a float checkpoint to KneadedWeight.

    Conv layers knead their im2col [C*kh*kw, out_ch] matrices; arbitrary
    reduction dims are zero-padded to the lcm(32, ks) / n_block alignment
    (exact — padding has occupancy 0 and is skipped by the kernel).  Biases
    stay float: the paper kneads the weight stream only.
    """
    out: Dict = {}
    for name, p in params.items():
        out[name] = {"w": knead_padded(p["w"], bits=bits, ks=ks,
                                       n_block=n_block),
                     "b": p["b"]}
    return out


def shard_kneaded_params(kparams: Dict, mesh, axis: str = "model",
                         partition: str = "contiguous") -> Dict:
    """Partition every KneadedWeight of a kneaded checkpoint along N.

    Each layer's compacted schedule splits into per-device work lists
    (:func:`repro.core.schedule.shard_schedule`); biases stay whole
    (replicated — every device's epilogue adds its output-column slice).
    ``partition="balanced"`` LPT-packs each layer's tiles on static
    occupancy instead of contiguous slabs (docs/DESIGN.md §11).  Place the
    result with ``runtime.sharding.kneaded_shardings`` before serving.
    """
    return {name: {"w": shard_schedule(p["w"], mesh, axis=axis,
                                       partition=partition),
                   "b": p["b"]}
            for name, p in kparams.items()}


def weight_matrices(params: Dict) -> Dict[str, jax.Array]:
    """Every layer as its [K, N] matmul matrix (the kneading target)."""
    return {name: p["w"] for name, p in params.items()}


def train_briefly(key, cfg: CNNConfig, steps: int = 30, batch: int = 32,
                  lr: float = 1e-2) -> Dict:
    """A few SGD steps on a synthetic-but-learnable task, so weight
    statistics resemble trained (leptokurtic) weights rather than the init
    Gaussian — the paper measures *trained* Caffe models."""
    params = init(key, cfg)
    kdata = jax.random.split(key, steps)

    def loss_fn(p, x, y):
        logits = apply(p, x, cfg)
        return jnp.mean(
            -jax.nn.log_softmax(logits)[jnp.arange(x.shape[0]), y])

    @jax.jit
    def step(p, k):
        x = jax.random.normal(k, (batch, cfg.image_size, cfg.image_size,
                                  cfg.in_channels))
        # learnable rule: class = argmax of channel-mean patches
        y = jnp.argmax(jnp.mean(x, axis=(1, 2)), axis=-1) % cfg.num_classes
        g = jax.grad(loss_fn)(p, x, y)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g)

    for k in kdata:
        params = step(params, k)
    return params
