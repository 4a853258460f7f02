"""Serving launcher: batched generation with optional kneaded weights.

``python -m repro.launch.serve --arch smollm-360m --quant 8 --tokens 32``
trains nothing: initializes (or restores) params, kneads them to the
requested precision, and serves a batch of synthetic prompts — the
end-to-end demonstration of the paper's technique as a serving feature.
``--smoke`` (the default) serves the arch's reduced CPU-size config;
``--full`` serves its published config.
``--impl pallas`` serves through the fully-kneaded bit-plane path (the SAC
kernel's decode-GEMV fast path, docs/DESIGN.md §7); the default "quant"
keeps the integer-matmul form selected by ``--quant``.  ``--shards N``
partitions every kneaded projection's compacted schedule over an N-device
"model" mesh (docs/DESIGN.md §8; on CPU force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* launch).

``--scheduler continuous`` routes the same prompts through the request
front end's continuous-batching slot scheduler (docs/DESIGN.md §9) with
``--max-inflight`` in-flight slots; ``--stream`` prints the first request's
tokens as they decode.  Both schedulers print the queue-wait vs decode-time
latency breakdown (p50/p95) from ``latency_stats()`` so they are directly
comparable from the CLI.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="serve the arch's reduced config (default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="serve the arch's published config")
    ap.add_argument("--quant", type=int, default=0, choices=[0, 8, 4])
    ap.add_argument("--impl", default="quant",
                    choices=["quant", "float", "int", "planes", "pallas"],
                    help="serving path: quantized matmuls (quant) or the "
                         "kneaded SAC forms (int/planes/pallas)")
    ap.add_argument("--knead-min-dim", type=int, default=128,
                    help="skip kneading projections smaller than this "
                         "(lower it for smoke-size archs)")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard kneaded schedules over this many 'model'-"
                         "mesh devices (requires --impl pallas)")
    ap.add_argument("--expert-shards", type=int, default=0,
                    help="shard kneaded MoE expert banks over this many "
                         "'expert'-mesh devices (whole experts per device; "
                         "composes with --shards into a 2-D "
                         "('expert','model') mesh; requires a kneaded impl "
                         "and num_experts %% expert_shards == 0; "
                         "docs/DESIGN.md §13)")
    ap.add_argument("--shard-partition", default="contiguous",
                    choices=["contiguous", "balanced"],
                    help="tile→shard partitioning of sharded schedules: "
                         "contiguous N-tile slabs, or occupancy-balanced "
                         "LPT bin-packing with a recorded permutation "
                         "(bit-exact either way; docs/DESIGN.md §11)")
    ap.add_argument("--activation-skip", action="store_true",
                    help="arm the runtime activation-side skip (two-sided "
                         "skip, docs/DESIGN.md §12): per-K-tile presence "
                         "bits from the decode activation row are "
                         "intersected into every kneaded projection's "
                         "schedule walk, so work items whose activation "
                         "slice is all zero never execute.  Decode-GEMV "
                         "steps only (prefill keeps the static weight-only "
                         "skip); bit-exact on/off.  Effective with the "
                         "kneaded impls (int/planes/pallas); reports "
                         "act_skip_frac in the latency stats")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a training checkpoint dir")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduler", default="batch",
                    choices=["batch", "continuous"],
                    help="request scheduler: wave-synchronous padding-"
                         "bucket drain (batch) or the step-level slot "
                         "scheduler with a paged KV pool (continuous)")
    ap.add_argument("--max-inflight", type=int, default=8,
                    help="continuous scheduler: in-flight slot capacity")
    ap.add_argument("--stream", action="store_true",
                    help="print the first request's tokens as they decode")
    # resilience knobs (docs/DESIGN.md §10) — any of them arms the fault
    # policy: bounded retries + NaN quarantine + step watchdog + demotion
    ap.add_argument("--max-retries", type=int, default=None,
                    help="arm the fault policy: per-request recovery "
                         "attempts before the terminal FAILED state")
    ap.add_argument("--step-timeout", type=float, default=None,
                    help="watchdog threshold in seconds on one decode "
                         "launch (counts watchdog_timeouts in stats)")
    ap.add_argument("--fallback-impl", default=None,
                    help="comma-separated degradation ladder, strongest "
                         "first (default 'planes,float'): repeated step "
                         "faults demote --impl down this ladder")
    args = ap.parse_args()

    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.checkpoint import checkpointer as ckpt
    from repro.configs.registry import get_config
    from repro.inference.engine import (ServingConfig, ServingEngine,
                                        serving_bytes)
    from repro.models.lm import LanguageModel

    cfg = get_config(args.arch, smoke=args.smoke)
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        step = ckpt.latest_step(args.ckpt_dir)
        if step is not None:
            from repro.optim import adamw
            from repro.train.step import TrainStepConfig
            like = {"params": params,
                    "opt": adamw.init(params,
                                      TrainStepConfig().optimizer)}
            params = ckpt.restore(args.ckpt_dir, step, like)["params"]
            print(f"restored step {step} from {args.ckpt_dir}")

    fault_policy = None
    if (args.max_retries is not None or args.step_timeout is not None
            or args.fallback_impl is not None):
        from repro.inference.resilience import ServingFaultPolicy
        fault_policy = ServingFaultPolicy(
            max_retries=(args.max_retries if args.max_retries is not None
                         else 2),
            step_timeout_s=args.step_timeout or 0.0,
            fallback_impls=(tuple(args.fallback_impl.split(","))
                            if args.fallback_impl
                            else ("planes", "float")),
            verify_weights=bool(args.ckpt_dir))

    eng = ServingEngine(cfg, params, ServingConfig(
        max_len=args.prompt_len + args.tokens + 8,
        quant_bits=args.quant, temperature=args.temperature,
        impl=args.impl, knead_min_dim=args.knead_min_dim,
        shards=args.shards, shard_partition=args.shard_partition,
        expert_shards=args.expert_shards,
        activation_skip=args.activation_skip,
        scheduler=args.scheduler,
        max_inflight=args.max_inflight, fault_policy=fault_policy))
    if args.impl in ("int", "planes", "pallas"):
        precision = f"kneaded int{args.quant or 8}"   # engine default: 8
    elif args.impl == "float":
        precision = "bf16"
    else:
        precision = f"int{args.quant}" if args.quant else "bf16"
    shard_note = f", {args.shards}-way model mesh" if args.shards > 1 else ""
    if args.expert_shards > 1:
        shard_note += f", {args.expert_shards}-way expert mesh"
    print(f"serving params: {serving_bytes(eng.params)/1e6:.2f} MB "
          f"(impl={args.impl}, {precision}{shard_note})")
    work = eng.expert_work_table()
    for path, table in work.items():
        per_e = table.sum(axis=tuple(range(table.ndim - 1)))
        imb = float(per_e.max() / max(per_e.mean(), 1e-9))
        print(f"expert work {path}: per-expert tile-dots "
              f"{per_e.tolist()} (imbalance {imb:.2f}x)")

    key = jax.random.PRNGKey(7)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            key, (args.batch, cfg.num_image_tokens, cfg.d_model))

    t0 = time.perf_counter()
    if cfg.family in ("encdec", "vlm") or (args.scheduler == "batch"
                                           and not args.stream):
        out = eng.generate(batch, args.tokens)
        rows = [r.tolist() for r in out[:2]]
    else:
        # route through the request front end so the scheduler choice
        # (and per-request stats) actually exercises
        handles = [eng.submit(prompts[i], args.tokens)
                   for i in range(args.batch)]
        if args.stream:
            print("streaming request 0:", end=" ", flush=True)
            for tok in handles[0].stream():
                print(tok, end=" ", flush=True)
            print()
        eng.drain()
        rows = [h.result().tolist() for h in handles[:2]]
    dt = time.perf_counter() - t0
    print(f"generated [{args.batch} x {args.tokens}] in {dt:.2f}s "
          f"({args.batch*args.tokens/dt:.1f} tok/s, "
          f"scheduler={args.scheduler})")
    for row in rows:
        print("  ", row)
    stats = eng.latency_stats()
    if stats["requests"]:
        print(f"latency p50/p95: {stats['p50_ms']:.1f}/"
              f"{stats['p95_ms']:.1f} ms over {stats['requests']} requests")
        if "queue_wait_p50_ms" in stats:
            print(f"  queue wait p50/p95: {stats['queue_wait_p50_ms']:.1f}/"
                  f"{stats['queue_wait_p95_ms']:.1f} ms | decode p50/p95: "
                  f"{stats['decode_p50_ms']:.1f}/"
                  f"{stats['decode_p95_ms']:.1f} ms")
        if "ttft_p50_ms" in stats:
            print(f"  time to first token p50/p95: "
                  f"{stats['ttft_p50_ms']:.1f}/{stats['ttft_p95_ms']:.1f} ms")
    if "routed_tokens" in stats:
        print(f"routing: {stats['routed_tokens']} tokens routed over "
              f"{stats['routing_steps']} steps, "
              f"{stats['capacity_dropped']} dropped at capacity")
    if args.activation_skip and "act_skip_frac" in stats:
        print(f"activation skip: {stats['executed_tile_dots']} of "
              f"{stats['weight_tile_dots']} scheduled tile-dots executed "
              f"(act_skip_frac={stats['act_skip_frac']:.3f})")
    if fault_policy is not None:
        fault_keys = ("retries", "failed_requests", "recoveries",
                      "nan_quarantined", "watchdog_timeouts",
                      "straggler_steps", "degradations",
                      "integrity_repairs")
        counters = {k: stats[k] for k in fault_keys if k in stats}
        print(f"fault counters: {counters or 'clean'} "
              f"(impl now {eng.scfg.impl})")


if __name__ == "__main__":
    main()
