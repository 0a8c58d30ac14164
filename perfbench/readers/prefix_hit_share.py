"""Prompt tokens adopted from the prefix cache over all prompt tokens
admitted, summed from the ``engine.admit`` spans of the window. %"""
from perfbench.programs import spans


def read(facts):
    events = spans.windowed(facts, trace=spans.ENGINE)
    if events is None:
        return None
    admits = [e for e in events if e["name"] == "engine.admit"]
    total = sum(spans.arg(e, "prompt_tokens") for e in admits)
    if not total:
        return None
    return 100.0 * sum(spans.arg(e, "prefix_hit_tokens")
                       for e in admits) / total
