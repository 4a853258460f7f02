"""The SAC kernel's share of its roofline in the CNN: the least time of every
conv (im2col rows x C*k*k x out) and fc call of the traced images (f32
activations; ``counts.least_time_s``) over the device time of the kernel's
ops inside ``jit_fwd`` runs."""
from benchmarks.chip import counts, trace


def read(record, peaks=None, **_):
    s = record.get("summary")
    if s is None or peaks is None or not record.get("images"):
        return None
    kernel = s.op_seconds(trace.is_sac, within="fwd")
    if kernel <= 0:
        return None
    calls = counts.cnn_kneaded_calls(record["model"], record["images"])
    least, _, _ = counts.least_time_s(calls, 4, peaks)
    return 100.0 * least / kernel
