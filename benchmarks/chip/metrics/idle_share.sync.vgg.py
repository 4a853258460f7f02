"""Share of the traced stretch in which the chip sat idle while the program
waited on it: the innermost ``serve.*`` span open over the idle time is a
``*_sync`` one (``spans.py``), vgg cell.  A part of ``idle_share.vgg``."""
from benchmarks.chip import spans


def read(record, **_):
    return spans.idle_share(record.get("summary"), sync=True)
