"""The SAC kernel's share of its roofline in decode: the least time of every
kneaded projection the traced decode steps ran (live rows, logical K and N,
bf16 activations; ``counts.least_time_s``) over the device time of the
kernel's ops inside ``jit_decode_step`` runs."""
from benchmarks.chip import counts, trace


def read(record, peaks=None, **_):
    s = record.get("summary")
    if s is None or peaks is None:
        return None
    kernel = s.op_seconds(trace.is_sac, within="decode_step")
    calls = [c for st in record["steps"] if st["contexts"]
             for c in counts.lm_kneaded_calls(record["model"],
                                              len(st["contexts"]))]
    if kernel <= 0 or not calls:
        return None
    least, _, _ = counts.least_time_s(calls, 2, peaks)
    return 100.0 * least / kernel
