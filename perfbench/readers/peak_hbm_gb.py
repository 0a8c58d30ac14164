"""Peak bytes in use on the fullest chip, as PJRT reports them when
the window has closed (program temporaries are not in it). GB."""


def read(facts):
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
