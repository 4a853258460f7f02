"""The trace reduction on a small hand-made trace: busy union, idle share,
self time of nested ops, per-step attribution and idle gaps by host span."""
import pytest

import bench_chip_small  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import trace as tr

SAC = ('%_run.1 = f32[8,128] custom-call(bf16[8,256] %a), '
       'custom_call_target="tpu_custom_call"')
MS = 1_000_000


def _trace():
    # one chip: a decode step 0-10 ms holding a while (1-9 ms) that holds a
    # SAC call (2-5) and a fusion (5-8); a prefill step 20-30 ms with one
    # SAC call (21-29); nothing runs 10-20 and 30-40.
    ops = [tr.Op("%while.2 = (s32[]) while(...)", 1 * MS, 9 * MS),
           tr.Op(SAC, 2 * MS, 5 * MS),
           tr.Op("%fusion.7 = bf16[8] fusion(...)", 5 * MS, 8 * MS),
           tr.Op("%copy.1 = f32[4] copy(...)", 0, 1 * MS),
           tr.Op(SAC.replace("_run.1", "_run.2"), 21 * MS, 29 * MS)]
    mods = [tr.Op("jit_decode_step(123)", 0, 10 * MS),
            tr.Op("jit_prefill(456)", 20 * MS, 30 * MS)]
    spans = [tr.Op("traced_window", 0, 40 * MS),
             tr.Op("scheduler_step", 0, 31 * MS),
             tr.Op("wait_for_arrival", 32 * MS, 40 * MS)]
    return tr.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}}, spans)


def test_busy_union_and_idle_share():
    s = tr.Summary(_trace(), (0, 40 * MS))
    assert s.window_s == pytest.approx(0.040)
    # busy: 0-9 (copy, while and its children) + 21-29
    assert s.busy_s == pytest.approx(0.017)
    assert 1 - s.busy_s / s.window_s == pytest.approx(23 / 40)


def test_window_clips_ops():
    s = tr.Summary(_trace(), (4 * MS, 24 * MS))
    assert s.busy_s == pytest.approx((9 - 4 + 24 - 21) * 1e-3)


def test_self_time_of_nested_ops():
    s = tr.Summary(_trace(), (0, 40 * MS))
    top = dict(s.top_ops())
    # 8 ms minus 6 nested
    assert top["decode_step %while.2"] == pytest.approx(0.002)
    assert top["prefill sac_matmul_kernel %_run.2 f32[8,128] custom-call"] \
        == pytest.approx(0.008)
    assert top["decode_step %fusion.7 bf16[8] fusion"] == pytest.approx(0.003)


def test_kernel_time_per_step():
    s = tr.Summary(_trace(), (0, 40 * MS))
    assert s.op_seconds(tr.is_sac) == pytest.approx(0.011)
    assert s.op_seconds(tr.is_sac, within="decode_step") == \
        pytest.approx(0.003)
    assert s.op_seconds(tr.is_sac, within="prefill") == pytest.approx(0.008)
    assert [m.name for m in s.modules("decode_step")] == \
        ["jit_decode_step(123)"]


def test_idle_gaps_by_host_span():
    s = tr.Summary(_trace(), (0, 40 * MS))
    gaps = dict(s.idle_by_span())
    # 9-21 ms inside scheduler_step; 29-40 ms: 29-31 scheduler_step (its
    # midpoint 34.5 falls in wait_for_arrival, so the gap counts there)
    assert gaps["scheduler_step"] == pytest.approx(0.012)
    assert gaps["wait_for_arrival"] == pytest.approx(0.011)


def test_span_at_innermost():
    t = _trace()
    assert t.span_at(5 * MS) == "scheduler_step"
    assert t.span_at(31_500_000) == "traced_window"
    assert t.span_at(35 * MS) == "wait_for_arrival"
    assert t.span_at(50 * MS) == "no host span"


def _reader(name):
    from benchmarks.chip import common
    return common.load_module(common.HERE / "metrics" / f"{name}.py")


def test_im2col_share_reads_output_fusions_of_the_forward():
    # a forward 0-10 ms: the patch convolution (an output fusion, 1-4 ms),
    # a SAC call (4-9 ms) and a loop fusion (9-10 ms); an output fusion
    # outside any forward run does not count
    conv = ("%fusion.5 = f32[1,224,224,27]{3,0,2,1} fusion(f32[1,224,224,3] "
            "%copy.6, bf16[3,3,27] %bitcast.35), kind=kOutput, "
            "calls=%fused_computation.9")
    ops = [tr.Op(conv, 1 * MS, 4 * MS), tr.Op(SAC, 4 * MS, 9 * MS),
           tr.Op("%add_maximum_fusion = f32[8] fusion(...), kind=kLoop",
                 9 * MS, 10 * MS),
           tr.Op(conv, 20 * MS, 22 * MS)]
    mods = [tr.Op("jit_fwd(1)", 0, 10 * MS)]
    t = tr.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}}, [])
    s = tr.Summary(t, (0, 40 * MS))
    share = _reader("im2col_share").read({"summary": s})
    assert share == pytest.approx(100 * 3 / 11)
    assert _reader("im2col_share").read({"summary": None}) is None


def test_decode_step_ms_and_idle_share_readers():
    s = tr.Summary(_trace(), (0, 40 * MS))
    assert _reader("decode_step_ms").read({"summary": s}) == \
        pytest.approx(10.0)
    assert _reader("idle_share.chat").read({"summary": s}) == \
        pytest.approx(100 * 23 / 40)
    assert _reader("decode_step_ms").read({"summary": None}) is None


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]


@pytest.mark.parametrize("name,expect", [
    ("%copy-start.1 = (bf16[2]{0}, bf16[2]{0}, u32[]) copy-start(bf16[2] %x)",
     True),
    ("%copy-done = bf16[2]{0} copy-done((bf16[2], u32[]) %copy-start)", True),
    ("%all-reduce-start = f32[8] all-reduce-start(f32[8] %p)", True),
    ("%while.2 = (s32[]{:T(128)}, bf16[64,1,960]{2,0,1:T(8,128)(2,1)S(1)}) "
     "while((s32[]) %t), condition=%c", False),
    ("%fusion.5 = f32[4]{0} fusion(f32[4] %copy-start.6), kind=kOutput", False),
    (SAC, False)])
def test_async_ops_are_not_core_work(name, expect):
    assert tr.is_async(name) is expect


def test_itl_reader_takes_the_p95_of_all_gaps():
    gaps = [50.0] * 95 + [150.0] * 5
    assert _reader("itl_p95_ms").read({"token_gaps_ms": gaps}) == \
        pytest.approx(50.0 + 100.0 * 0.05)
    assert _reader("itl_p95_ms").read({"token_gaps_ms": []}) is None


def test_capture_keeps_the_harness_spans(tmp_path):
    """A capture with the Python tracer off still holds the harness's own
    spans, on the host line of the thread that opened them."""
    import jax
    import jax.numpy as jnp

    with tr.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation("traced_window"):
            with jax.profiler.TraceAnnotation("scheduler_step"):
                jnp.ones(8).block_until_ready()
    t = tr.Trace.from_file(tr.xplane_path(str(tmp_path)), "traced_window")
    lo, hi = t.span_bounds("traced_window")
    inner = t.span_bounds("scheduler_step")
    assert inner is not None and lo <= inner[0] <= inner[1] <= hi
