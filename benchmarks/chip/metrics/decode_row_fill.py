"""Useful share of the decode rows: 100 x the live slots over the rows of
the decode batch (the slot bucket), summed over the ``serve.decode`` spans
that start in the traced stretch (their ``live`` and ``rows`` counters).
None without such spans; decode spans whose counters cannot be read raise
(``spans.stats``)."""
from benchmarks.chip import spans


def read(record, **_):
    s = record.get("summary")
    if s is None:
        return None
    decodes = [o for o in spans.serve_spans(s) if o.name == "serve.decode"]
    if not decodes:
        return None
    counters = spans.stats(s)
    live = rows = 0
    for o in decodes:
        c = counters[(o.name, o.start)]
        live += c["live"]
        rows += c["rows"]
    return 100.0 * live / rows if rows else None
