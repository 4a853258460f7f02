"""Share of the traced stretch in which no op ran on the chip (1 minus the
union of op intervals over the stretch), chat cell."""


def read(record, **_):
    s = record.get("summary")
    if s is None or not s.has_device:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
