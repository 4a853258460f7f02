"""The readers of the program's own spans (``spans.py`` and the metrics
``idle_share.{sync,host}.*``, ``admit_p90_ms``, ``decode_row_fill``) on
small hand-made traces, and the counters read back from a real capture."""
import pytest

import bench_chip_small  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import common, spans
from benchmarks.chip import trace as tr

MS = 1_000_000


def _reader(name):
    return common.load_module(common.HERE / "metrics" / f"{name}.py")


def _summary(ops, mods, host, window):
    dev = {"ops": [tr.Op(n, a * MS, b * MS) for n, a, b in ops],
           "modules": [tr.Op(n, a * MS, b * MS) for n, a, b in mods]}
    t = tr.Trace({"/device:TPU:0": dev},
                 [tr.Op(n, a * MS, b * MS) for n, a, b in host])
    return tr.Summary(t, (window[0] * MS, window[1] * MS))


def _chat():
    # one scheduler step that admits a group and decodes; the device runs
    # the prefill 4-14 ms, a cache copy 22-28 and the decode 33-45; then
    # the harness waits for arrivals.  Idle: 0-4, 14-22, 28-33, 45-100.
    host = [("traced_window", 0, 100), ("scheduler_step", 0, 60),
            ("serve.step", 1, 59),
            ("serve.admit", 2, 30),
            ("serve.prefill", 2, 10), ("PjitFunction(prefill)", 3, 5),
            ("serve.prefill_sync", 10, 20),
            ("np.asarray(jax.Array)", 11, 19),
            ("serve.cache_write", 20, 30), ("PjitFunction(_pad)", 21, 22),
            ("serve.decode", 30, 35), ("PjitFunction(decode_step)", 31, 33),
            ("serve.token_sync", 35, 50),
            ("np.asarray(jax.Array)", 36, 49),
            ("serve.retire", 50, 58),
            ("wait_for_arrival", 62, 100)]
    ops = [("%fusion.1 = bf16[8] fusion(...)", 4, 14),
           ("%copy.3 = bf16[8] copy(...)", 22, 28),
           ("%fusion.2 = bf16[8] fusion(...)", 33, 45)]
    mods = [("jit_prefill(1)", 4, 14), ("jit_scatter(2)", 22, 28),
            ("jit_decode_step(3)", 33, 45)]
    return _summary(ops, mods, host, (0, 100))


def _vgg():
    # submit 1-4, then a drain: batch 5-8, forward 8-10, the wait 10-40,
    # finish 40-44; the forward runs 9-38.  Idle: 0-9, 38-50.
    host = [("traced_window", 0, 50), ("submit", 1, 4), ("drain", 5, 45),
            ("serve.batch", 5, 8), ("serve.forward", 8, 10),
            ("PjitFunction(fwd)", 8, 9), ("serve.forward_sync", 10, 40),
            ("serve.finish", 40, 44)]
    ops = [("%fusion.6 = f32[1] fusion(...)", 9, 38)]
    return _summary(ops, [("jit_fwd(1)", 9, 38)], host, (0, 50))


# sync: 14-20 under prefill_sync, 45-50 under token_sync (inside its
# np.asarray child); host: 1-4, 20-22, 28-33, 50-59; 0-1 and 59-100 are
# the harness's own
@pytest.mark.parametrize("name,summary,want", [
    ("idle_share.sync.chat", _chat, 11.0),
    ("idle_share.host.chat", _chat, 19.0),
    ("idle_share.chat", _chat, 72.0),
    ("idle_share.sync.vgg", _vgg, 100 * 2 / 50),
    ("idle_share.host.vgg", _vgg, 100 * 8 / 50),
    ("idle_share.vgg", _vgg, 100 * 21 / 50)])
def test_idle_share_readers(name, summary, want):
    assert _reader(name).read({"summary": summary()}) == pytest.approx(want)


@pytest.mark.parametrize("cell,summary", [("chat", _chat), ("vgg", _vgg)])
def test_sync_and_host_are_parts_of_the_idle_share(cell, summary):
    s = summary()
    parts = [_reader(f"idle_share.{k}.{cell}").read({"summary": s})
             for k in ("sync", "host")]
    assert sum(parts) <= _reader(f"idle_share.{cell}").read({"summary": s})


def test_idle_by_serve_splits_the_waits():
    by_span = spans.idle_by_serve(_chat())
    assert by_span == pytest.approx({
        "serve.prefill_sync": 0.006, "serve.token_sync": 0.005,
        "serve.step": 0.002, "serve.prefill": 0.002,
        "serve.cache_write": 0.004, "serve.decode": 0.003,
        "serve.retire": 0.008})


def test_innermost_serve_span_skips_other_spans():
    segs = spans.segments(_chat().trace.spans)
    assert spans.innermost_serve(segs, 40 * MS) == "serve.token_sync"
    assert spans.innermost_serve(segs, 3 * MS) == "serve.prefill"
    assert spans.innermost_serve(segs, 25 * MS) == "serve.cache_write"
    assert spans.innermost_serve(segs, 59 * MS) is None
    assert spans.innermost_serve(segs, 0) is None


def _admits_and_decodes():
    host = [("traced_window", 0, 400)]
    host += [("serve.admit", 10 + 60 * i, 10 + 60 * i + d)
             for i, d in enumerate((10, 20, 30, 40, 50))]
    host += [("serve.admit", -50, -10)]        # before the stretch
    host += [("serve.decode", 50, 52), ("serve.decode", 110, 112),
             ("serve.decode", 170, 172), ("serve.decode", -5, -3)]
    s = _summary([("%f = f32[1] fusion(...)", 0, 1)], [], host, (0, 400))
    s.span_stats = {("serve.decode", 50 * MS): {"live": 46, "rows": 64},
                    ("serve.decode", 110 * MS): {"live": 40, "rows": 64},
                    ("serve.decode", 170 * MS): {"live": 3, "rows": 4},
                    ("serve.decode", -5 * MS): {"live": 1, "rows": 64}}
    return s


def test_admit_p90_ms_reads_the_admissions_of_the_stretch():
    # p90 of 10, 20, 30, 40, 50 ms: 40 + 0.6 x 10
    assert _reader("admit_p90_ms").read({"summary": _admits_and_decodes()}) \
        == pytest.approx(46.0)


def test_decode_row_fill_sums_live_over_rows():
    assert _reader("decode_row_fill").read(
        {"summary": _admits_and_decodes()}) == \
        pytest.approx(100 * (46 + 40 + 3) / (64 + 64 + 4))


@pytest.mark.parametrize("name", [
    "idle_share.sync.chat", "idle_share.host.chat", "admit_p90_ms",
    "decode_row_fill", "idle_share.sync.vgg", "idle_share.host.vgg"])
def test_readers_are_silent_without_program_spans(name, tmp_path,
                                                  monkeypatch):
    """A program that opens no ``serve.*`` span (and no capture to read)
    gives nothing, and nothing raises."""
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    s = _summary([("%f = f32[1] fusion(...)", 0, 5)], [],
                 [("traced_window", 0, 10), ("scheduler_step", 1, 9)],
                 (0, 10))
    assert _reader(name).read({"summary": s}) is None
    assert _reader(name).read({"summary": None}) is None


def test_counters_come_back_from_the_run_capture(tmp_path, monkeypatch):
    """``spans.stats`` finds the capture of the run's scratch directory
    whose window span is the summary's stretch, and reads the counters."""
    import tempfile

    import jax

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    dirs = []
    for live in (5, 7):         # this run's capture, then a later one
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        dirs.append(log_dir)
        with tr.capture(log_dir):
            with jax.profiler.TraceAnnotation("traced_window"):
                for _ in range(2):
                    with jax.profiler.TraceAnnotation(
                            "serve.decode", live=live, rows=8):
                        pass
                with jax.profiler.TraceAnnotation("serve.admit", n=3,
                                                  bucket=4, plen=128):
                    pass
    t = tr.Trace.from_file(tr.xplane_path(dirs[0]), "traced_window")
    s = tr.Summary(t, t.span_bounds("traced_window"))
    assert _reader("decode_row_fill").read({"summary": s}) == \
        pytest.approx(100 * 5 / 8)
    adm = [v for (name, _), v in spans.stats(s).items()
           if name == "serve.admit"]
    assert adm == [{"n": 3, "bucket": 4, "plen": 128}]


def test_decode_spans_without_their_capture_raise(tmp_path, monkeypatch):
    """Decode spans whose capture cannot be found are a fault of the
    harness, not a program without spans: the reader raises."""
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    s = _summary([("%f = f32[1] fusion(...)", 0, 5)], [],
                 [("traced_window", 0, 10), ("serve.decode", 1, 3)],
                 (0, 10))
    with pytest.raises(RuntimeError, match="serve.* spans"):
        _reader("decode_row_fill").read({"summary": s})
