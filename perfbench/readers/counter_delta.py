"""How far one of the program's counters moved from the end of warm-up
to the end of the drain, lead-in included (the adapter's ``counters()``
before and after, as ``batch_occupancy`` reads its own). Nothing where
the program keeps no such counter."""


def read(facts, key):
    a, b = facts.get("engine_start"), facts.get("engine_end")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]
