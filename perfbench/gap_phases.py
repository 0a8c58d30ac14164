"""Which phase of the program's loop the host was in while the device
sat idle.

    python3 -m perfbench.gap_phases <trace directory or .xplane.pb> [--top 20]

Stand-alone: ``run.py`` does not call it. While a profiler session is
active the program writes its iteration phases onto the trace's host
plane under their own names (``engine.iter`` and its children
``engine.admit``, ``.prefill``, ``.reserve``, ``.dispatch``, ``.wait``,
``.emit``; ``engine.idle``; ``train.dispatch``), so they are on the
device operations' clock and need no mapping. This prints, for the
longest gaps between device operations, the innermost phase that covers
the middle of each, and the idle seconds by phase over all gaps of the
trace. Idle time that falls in no phase is the loop's own few lines
between two iterations, or a program that writes no phases.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

from . import trace_reduce

PREFIXES = ("engine.", "train.")
# a parent covers its children: time in it and in none of them is its own
PARENTS = ("engine.iter",)
OUTSIDE = "outside_any_phase"


def phase_spans(planes):
    """{phase name: merged [start, end] intervals} from the host planes."""
    by_name = {}
    for plane, lines in planes.items():
        if plane.startswith("/host:"):
            for name, start, dur in lines.get("host", []):
                if name.startswith(PREFIXES):
                    by_name.setdefault(name, []).append((start, start + dur))
    return {k: trace_reduce.union(v) for k, v in by_name.items()}


def device_gaps(planes):
    """One merged, sorted list of [start, end] gaps between busy
    stretches for each device plane."""
    out = []
    for plane, lines in sorted(planes.items()):
        if plane.startswith("/device:TPU:"):
            busy = trace_reduce.union(
                [(s, s + d) for _, s, d in lines.get("XLA Ops", [])])
            out.append([[a[1], b[0]] for a, b in zip(busy, busy[1:])])
    return out


def _intersect(merged_a, merged_b):
    """Merged intersection of two merged interval lists."""
    out, j = [], 0
    for s, e in merged_a:
        while j < len(merged_b) and merged_b[j][1] <= s:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            out.append([max(s, merged_b[k][0]), min(e, merged_b[k][1])])
            k += 1
    return out


def innermost(phases, t):
    """The phase covering time ``t``: a child before its parent."""
    found = [name for name, spans in phases.items()
             if any(s <= t <= e for s, e in spans)]
    inner = [n for n in found if n not in PARENTS]
    return (inner or found or [OUTSIDE])[0]


def attribute(planes, top=20):
    """{"n_devices", "idle_s", "by_phase": {phase: seconds},
    "named_share", "longest": [[phase, seconds], ...]}; seconds are
    averaged over the devices, as ``trace_reduce`` averages busy time."""
    phases = phase_spans(planes)
    children = [k for k in phases if k not in PARENTS]
    in_children = trace_reduce.union(
        [tuple(iv) for k in children for iv in phases[k]])
    per_device = device_gaps(planes)
    total, by_phase = 0.0, {}
    for gaps in per_device:
        total += trace_reduce.length(gaps)
        for name in children:
            by_phase[name] = by_phase.get(name, 0.0) + trace_reduce.length(
                _intersect(gaps, phases[name]))
        for name in PARENTS:
            if name in phases:
                # a parent's own time: in it and in none of its children
                mine = _intersect(gaps, phases[name])
                by_phase[name] = by_phase.get(name, 0.0) \
                    + trace_reduce.subtract(mine, in_children)
    named = sum(by_phase.values())
    by_phase[OUTSIDE] = max(0.0, total - named)
    longest = sorted((g for gaps in per_device for g in gaps),
                     key=lambda g: g[0] - g[1])[:top]
    n, ns = max(1, len(per_device)), 1e-9
    return {
        "n_devices": len(per_device),
        "idle_s": total * ns / n,
        "by_phase": {k: v * ns / n for k, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
        "named_share": (named / total) if total else None,
        "longest": [[innermost(phases, (s + e) / 2.0), (e - s) * ns]
                    for s, e in longest],
    }


def find_trace(path):
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise SystemExit(f"gap_phases: {len(found)} traces under {path}")
    return found[0]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    out = attribute(trace_reduce.load(find_trace(args.trace)), args.top)
    print(f"idle {out['idle_s']:.4f} s a device over {out['n_devices']} "
          f"device(s); in a named phase: "
          f"{'nothing idle' if out['named_share'] is None else format(out['named_share'], '.1%')}")
    for name, sec in out["by_phase"].items():
        print(f"  {name:22s} {sec:9.4f} s")
    print(f"the {len(out['longest'])} longest gaps:")
    for name, sec in out["longest"]:
        print(f"  {sec * 1e3:9.3f} ms  {name}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
