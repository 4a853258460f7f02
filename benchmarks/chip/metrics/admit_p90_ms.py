"""Admission of a prompt group, p90 over the ``serve.admit`` spans that
start in the traced stretch (host wall time of the span: stacking the
prompts, the prefill dispatch, the first tokens' pull to the host and the
copy of the new rows into the KV cache)."""
from benchmarks.chip import spans
from benchmarks.chip.common import percentile


def read(record, **_):
    s = record.get("summary")
    if s is None:
        return None
    ms = [(o.end - o.start) * 1e-6 for o in spans.serve_spans(s)
          if o.name == "serve.admit"]
    return percentile(ms, 90) if ms else None
