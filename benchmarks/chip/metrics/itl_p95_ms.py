"""Inter-token gap, p95 over every gap between consecutive tokens of the
window's requests that ends in the traced stretch (host clock, the end of
the scheduler step that made each token; writing the trace stalls the host
after the stretch, so later gaps are left out).  The tail is the decode
step that also prefills a newly admitted group: the stall that chunked
prefill would shorten."""
from benchmarks.chip.common import percentile


def read(record, **_):
    gaps = record.get("token_gaps_ms")
    return percentile(gaps, 95) if gaps else None
