"""Scheduler queue wait, p90 over the requests due in the traced stretch:
the program's own admission stamp minus its submit stamp (host clock).
A request still queued at the end counts as beyond every limit."""
from benchmarks.chip.common import percentile


def read(record, **_):
    waits = record.get("queue_wait_ms")
    return percentile(waits, 90) if waits else None
