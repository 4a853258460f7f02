"""The paper's own domain end to end: a kneaded VGG-16 classifier.

Trains VGG-16 briefly, hands the float checkpoint to ``CNNServingEngine``,
which converts EVERY conv/fc layer to the kneaded bit-plane format (the
Tetris deployment artifact) and runs the whole forward pass through SAC —
then demonstrates the Pallas kernel path end to end on an AlexNet-16 and
reports:

  * classification agreement between float and kneaded inference,
  * the per-layer kneaded HBM footprint vs bf16 + kneaded cycle ratio,
  * bit-exactness of the Pallas kernel against the planes oracle.

Run:  PYTHONPATH=src python examples/cnn_kneaded.py

``--devices N`` (N >= 2) forces N host CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``) and additionally
runs the *sharded* serving path (docs/DESIGN.md §5): every AlexNet-16
layer's compacted schedule is partitioned along its out-channel dimension
over an N-device "model" mesh, the SAC kernel launches once per device
under ``jax.shard_map``, and the demo prints per-shard executed work plus
bit-exactness against the unsharded kernel:

    PYTHONPATH=src python examples/cnn_kneaded.py --devices 4

(The flag must be parsed before jax imports, which is why the heavy imports
live inside ``main``.)
"""
import argparse
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=1,
                    help="force N host CPU devices and demo the sharded "
                         "serving path (default 1: single device)")
    return ap.parse_args()


def main(args):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import cnn_weights
    from repro.inference.cnn_engine import CNNServingConfig, CNNServingEngine
    from repro.models import cnn

    cfg = cnn.CNN_ZOO["vgg16"]
    params = cnn_weights("vgg16")
    x = jax.random.normal(jax.random.PRNGKey(7),
                          (8, cfg.image_size, cfg.image_size, 3))

    float_eng = CNNServingEngine(cfg, params, CNNServingConfig(impl="float"))
    kneaded_eng = CNNServingEngine(cfg, params,
                                   CNNServingConfig(impl="int", bits=8))
    ref = float_eng.classify(x)
    pred = kneaded_eng.classify(x)
    agree = float(jnp.mean((pred == ref).astype(jnp.float32)))
    ratio = kneaded_eng.serving_bytes() / max(1, float_eng.serving_bytes())
    print(f"kneaded-int8 VGG-16: top-1 agreement with float = {100*agree:.0f}%"
          f"  (serving bytes = {ratio:.3f}x of bf16)")

    print(f"\n{'layer':>8} {'K x N':>14} {'kneaded/bf16':>13} {'cycles%':>8}")
    for row in kneaded_eng.layer_report(cycle_ks=16)[:8]:
        print(f"{row['layer']:>8} {str(row['shape']):>14} "
              f"{row['bytes_vs_bf16']:13.3f} {100*row['cycle_ratio']:8.1f}")

    # The Pallas kernel path, end to end (interpret mode on CPU): every
    # layer of an AlexNet-16 through the schedule-compacted SAC kernel —
    # one pallas_call per layer, dispatching only the occupied work items —
    # bit-exact against the paper-faithful planes decomposition.
    small = dataclasses.replace(cnn.CNN_ZOO["alexnet"], image_size=16)
    sparams = cnn.init(jax.random.PRNGKey(0), small)
    xs = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 16, 3))
    # the CPU interprets the kernel, which runs sooner eagerly than traced
    # under jit; on a TPU the kernel compiles, so jit the forward there
    jit = jax.default_backend() != "cpu"
    lg = CNNServingEngine(small, sparams,
                          CNNServingConfig(impl="pallas", jit=jit)).logits(xs)
    if args.devices == 1:
        lp = CNNServingEngine(small, sparams, CNNServingConfig(
            impl="planes", jit=jit)).logits(xs)
        exact = bool(np.array_equal(np.asarray(lg), np.asarray(lp)))
        print(f"\nalexnet-16 fully through the Pallas SAC kernel: "
              f"bit-exact vs planes oracle = {exact}")
    else:
        # forcing host devices re-partitions XLA CPU threading, which
        # perturbs the dense jnp oracle's f32 reduction order (the Pallas
        # kernel is bit-stable) — the oracle comparison only means anything
        # on one device; see docs/DESIGN.md §5
        print("\n(planes-oracle comparison skipped under forced host "
              "devices; see docs/DESIGN.md §5)")

    if args.devices > 1:
        # Sharded serving (docs/DESIGN.md §5): one schedule shard — and one
        # kernel launch under shard_map — per forced host device.
        assert jax.device_count() >= args.devices, jax.device_count()
        sh = CNNServingEngine(small, sparams, CNNServingConfig(
            impl="pallas", jit=jit, shards=args.devices))
        ls = sh.logits(xs)
        exact = bool(np.array_equal(np.asarray(ls), np.asarray(lg)))
        print(f"\nsharded over {args.devices} devices: bit-exact vs "
              f"single-device kernel = {exact}")
        print(f"{'layer':>8} {'per-shard executed work':>28} {'skew':>6}")
        for row in sh.layer_report():
            print(f"{row['layer']:>8} {str(row['shard_work']):>28} "
                  f"{row['shard_imbalance']:6.2f}")


if __name__ == "__main__":
    _args = parse_args()
    if _args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{_args.devices}").strip()
    main(_args)
