"""Device time of one decode step: the decode program's runs in the traced
stretch (``jit_decode_step`` modules), summed and divided by their count."""


def read(record, **_):
    s = record.get("summary")
    mods = s.modules("decode_step") if s is not None else []
    if not mods:
        return None
    return sum(m.end - m.start for m in mods) * 1e-6 / len(mods)
