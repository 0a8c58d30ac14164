"""Share of the device's busy time spent in operations whose name
matches a pattern (a Pallas kernel shows as ``<name>_custom-call``). %"""
import re


def read(facts, match):
    red = facts.get("trace")
    if not red or not red["busy_s"]:
        return None
    t = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    return 100.0 * t / red["busy_s"] if t else None
