"""The decode steps' share of the chip's bf16 peak: model operations of the
traced decode steps (2 x matmul weights incl. the head, per live row, plus
attention over each row's context; ``counts.lm_decode_flops``) over their
device time (``jit_decode_step`` modules) times the peak."""
from benchmarks.chip import counts


def read(record, peaks=None, **_):
    s = record.get("summary")
    mods = s.modules("decode_step") if s is not None else []
    if not mods or peaks is None:
        return None
    flops = sum(counts.lm_decode_flops(record["model"], st["contexts"])
                for st in record["steps"] if st["contexts"])
    secs = sum(m.end - m.start for m in mods) * 1e-9
    return 100.0 * flops / (secs * peaks["bf16_flops_per_s"])
