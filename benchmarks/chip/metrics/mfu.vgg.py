"""The forward's share of the chip's bf16 peak: 2 x the network's
multiply-adds per image (``counts.cnn_macs``) x the images of the traced
stretch, over the device time of their ``jit_fwd`` runs times the peak."""
from benchmarks.chip import counts


def read(record, peaks=None, **_):
    s = record.get("summary")
    mods = s.modules("fwd") if s is not None else []
    if not mods or peaks is None or not record.get("images"):
        return None
    flops = 2.0 * counts.cnn_macs(record["model"]) * record["images"]
    secs = sum(m.end - m.start for m in mods) * 1e-9
    return 100.0 * flops / (secs * peaks["bf16_flops_per_s"])
