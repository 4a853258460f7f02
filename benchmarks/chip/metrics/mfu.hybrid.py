"""A hybrid model's decode steps' share of the chip's bf16 peak: model
operations of the traced decode steps (2 x every kneaded projection and the
tied head per live row, attention over each row's context on the attention
layers, the Mamba-2 state update and read; ``counts_hybrid``) over their
device time (``jit_decode_step`` modules) times the peak."""
from benchmarks.chip import counts_hybrid


def read(record, peaks=None, **_):
    s = record.get("summary")
    mods = s.modules("decode_step") if s is not None else []
    if not mods or peaks is None or not record["model"].get("attn_period"):
        return None
    flops = sum(counts_hybrid.hybrid_decode_flops(record["model"],
                                                  st["contexts"])
                for st in record["steps"] if st["contexts"])
    secs = sum(m.end - m.start for m in mods) * 1e-9
    return 100.0 * flops / (secs * peaks["bf16_flops_per_s"])
