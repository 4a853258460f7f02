"""Share of the traced stretch in which the chip sat idle while the program
did host work: the innermost ``serve.*`` span open over the idle time is
not a ``*_sync`` one (``spans.py``), vgg cell.  A part of
``idle_share.vgg``; with ``idle_share.sync.vgg`` it leaves out only the
harness's own idle time (its ``submit`` and its wait for the reply)."""
from benchmarks.chip import spans


def read(record, **_):
    return spans.idle_share(record.get("summary"), sync=False)
