"""Share of the traced stretch in which the chip sat idle while the program
did host work: the innermost ``serve.*`` span open over the idle time is
not a ``*_sync`` one (``spans.py``), chat cell.  A part of
``idle_share.chat``; with ``idle_share.sync.chat`` it leaves out only the
harness's own idle time (arrival waits, its ``submit``)."""
from benchmarks.chip import spans


def read(record, **_):
    return spans.idle_share(record.get("summary"), sync=False)
