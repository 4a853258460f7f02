"""Distribution tests: sharding rules, multi-device correctness (subprocess
with a forced host-device count so the main test process keeps 1 device),
MoE EP equivalence, pipeline parallelism, HLO analysis."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.runtime import hlo_analysis, sharding

# --------------------------------------------------------------- HLO parser
_SAMPLE_HLO = """
HloModule jit_f

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %dot = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%dot), to_apply=%cond
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%i2, %ar)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_hlo_parser_trip_counts_and_dots():
    stats = hlo_analysis.analyze_hlo(_SAMPLE_HLO)
    # 10 iterations x (2*8*8*8) flops
    assert stats.dot_flops == pytest.approx(10 * 2 * 8 * 8 * 8)
    assert stats.collective_bytes["all-reduce"] == pytest.approx(
        10 * 8 * 8 * 4)


def test_hlo_parser_known_trip_count():
    hlo = _SAMPLE_HLO.replace(
        "while(%t0), condition=%cond, body=%body",
        'while(%t0), condition=%cond, body=%body, '
        'backend_config={"known_trip_count":{"n":"7"}}')
    stats = hlo_analysis.analyze_hlo(hlo)
    assert stats.dot_flops == pytest.approx(7 * 2 * 8 * 8 * 8)


# ------------------------------------------------------------ param specs
def _mk_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_param_spec_rules():
    mesh = jax.make_mesh((1,), ("data",))   # divisibility vacuous at size 1
    # on a 1-sized mesh everything divides; check the axis choices
    assert sharding.param_spec("layers/attn/wq", (32, 4096, 4096), mesh) \
        == P(None, ("data",), None)
    spec = sharding.param_spec("layers/mlp/wo", (32, 14336, 4096), mesh)
    assert spec == P(None, None, ("data",))   # reversed: model first (absent)


def test_param_spec_moe_and_embed():
    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    assert sharding.param_spec("layers/moe/wi", (4, 128, 512, 1024), mesh) \
        == P(None, "model", ("data",), None)
    assert sharding.param_spec("embed", (1024, 512), mesh) \
        == P("model", ("data",))
    assert sharding.param_spec("unembed", (512, 1024), mesh) \
        == P(("data",), "model")
    # indivisible dims fall back to None
    assert sharding.param_spec("layers/attn/wq", (2, 513, 1023), mesh) \
        == P(None, None, None)
    # sLSTM recurrent table is replicated by design
    assert sharding.param_spec("groups/slstm/r", (6, 4, 512, 2048), mesh) \
        == P()


def test_cache_sharding_seq_over_model():
    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    cache = {"k": jax.ShapeDtypeStruct((8, 4, 8192, 2, 16), jnp.bfloat16),
             "k_scale": jax.ShapeDtypeStruct((8, 4, 8192, 2), jnp.float32),
             "ssm": jax.ShapeDtypeStruct((8, 4, 5, 7), jnp.float32)}
    sh = sharding.cache_spec_sharding(cache, mesh, batch=4)
    assert sh["k"].spec == P(None, ("data",), "model", None, None)
    assert sh["k_scale"].spec == P(None, ("data",), "model", None)
    # small seq axes (SSM states) stay batch-only
    assert sh["ssm"].spec == P(None, ("data",), None, None)


# ----------------------------------------------- multi-device via subprocess
_SUBPROCESS_MOE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.models import blocks
    from repro.models.lm import LanguageModel
    from repro.runtime import pspec

    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)   # 8 experts, top-2
    model = LanguageModel(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab_size)}
    loss_1dev = float(model.loss(params, batch))         # no mesh: local MoE

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    from repro.runtime import sharding as shd
    pshard = shd.tree_shardings(jax.eval_shape(lambda: params), mesh)
    params_s = jax.tree.map(lambda x, s: jax.device_put(x, s), params, pshard)
    with pspec.axis_rules(mesh):
        loss_mesh = float(jax.jit(model.loss)(params_s, batch))
    print(json.dumps({"loss_1dev": loss_1dev, "loss_mesh": loss_mesh}))
""")


@pytest.mark.slow
def test_moe_ep_matches_single_device():
    """MoE expert-parallel dispatch under shard_map on a real 2x4 mesh must
    equal the single-device dispatch bit-for-bit (same capacity policy)."""
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_MOE], capture_output=True,
        text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".", timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(r["loss_1dev"] - r["loss_mesh"]) < 2e-2, r


_SUBPROCESS_PP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, json
    import jax.numpy as jnp
    from repro.runtime.pipeline import pipeline_apply

    mesh = jax.make_mesh((4,), ("pod",))
    L, M, mb, D = 8, 8, 2, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

    def layer(p, h):
        return jnp.tanh(h @ p)

    # reference: plain scan
    def ref_one(h):
        def body(c, p):
            return layer(p, c), None
        return jax.lax.scan(body, h, w)[0]
    ref = jax.vmap(ref_one)(x)

    out = pipeline_apply(layer, w, x, mesh, stage_axis="pod")
    err = float(jnp.max(jnp.abs(out - ref)))
    print(json.dumps({"err": err}))
""")


@pytest.mark.slow
def test_pipeline_parallel_matches_scan():
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PP], capture_output=True,
        text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".", timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["err"] < 1e-5, r


def test_compressed_psum_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, json
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_comp import compressed_psum

        mesh = jax.make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

        def f(xl):
            return compressed_psum(xl[0], "data")
        out = jax.shard_map(f, mesh=mesh, in_specs=P("data", None),
                            out_specs=P(), check_vma=False)(x)
        ref = jnp.sum(x, 0)
        rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        print(json.dumps({"rel": rel}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["rel"] < 0.05, r   # int8-compressed reduction, bounded error
