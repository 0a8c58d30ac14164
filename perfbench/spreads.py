"""The run-to-run spreads that the bounds of ``BENCHMARK.json`` were set
from, kept as data: ``perfbench/spreads/<cell>.json``, one file a cell,
which a later PR that adds a cell adds beside them.

A file holds, for each end-to-end metric of its cell but ``setup_s``,
the builder's sets of runs on the chip (``sets``: the values themselves,
six runs of one tree on six seeds a set, so that the spread is worked
out here and not copied) and the spreads the driver's own checks read
for that cell (``ledger``: the ledger's ``spread`` of a PR's line). The
driver's machines spread wider than one builder's lease, so a bound
follows the wider of the two. ``PERF.md`` section 2 states the rule:

    a bound is 2.5 to 3 times the widest spread on file, rounded up to
    a whole per cent, never under 1%; inside the driver's window it is
    at least twice that spread and at most eight times it (or 1%).

    python3 -m perfbench.spreads [--root <checkout>]

prints the table and fails where a bound has left the window.
"""

from __future__ import annotations

import argparse
import os
import sys

from . import manifest as manifest_mod
from . import stats

# the window the driver's two refusals state (PR 22, PR 23): a cell's
# runs may spread by at most half a bound, and a bound may be at most
# eight times the widest spread, or 1% where that is more
FLOOR_TIMES = 2.0
CEILING_TIMES = 8.0
ALWAYS_ALLOWED = 0.01


def on_file(root, cell):
    """{metric: {source: spread}} of one cell, or {} where it has no
    file: ``set <name>`` for each of the builder's sets (``quoted
    <origin>`` for one kept as a spread alone), ``ledger PR <n>`` for
    each of the driver's."""
    path = os.path.join(root, "perfbench", "spreads", cell + ".json")
    if not os.path.isfile(path):
        return {}
    data = manifest_mod.load_json(path)
    out = {}
    for metric, sets in data.get("sets", {}).items():
        for name, values in sets.items():
            out.setdefault(metric, {})[f"set {name}"] = stats.spread(values)
    for metric, lines in data.get("quoted", {}).items():
        for ln in lines:
            out.setdefault(metric, {})[f"quoted {ln['from']}"] = ln["spread"]
    for metric, lines in data.get("ledger", {}).items():
        for ln in lines:
            out.setdefault(metric, {})[f"ledger PR {ln['pr']}"] = ln["spread"]
    return out


def widest(root, metric, cells):
    """(spread, cell, source) of the widest spread on file for
    ``metric`` over ``cells``, or None where no file has it."""
    found = [(sp, cell, src) for cell in cells
             for src, sp in on_file(root, cell).get(metric, {}).items()]
    return max(found) if found else None


def check(root, out=print):
    """Hold every bound but ``setup_s``'s against the spreads on file;
    return the list of faults."""
    man = manifest_mod.Manifest(root)
    faults = []
    for m in man.data["end_to_end"]:
        if m["name"] == "setup_s":
            continue
        cells = m.get("workloads", [w["name"] for w in man.data["workloads"]])
        top = widest(root, m["name"], cells)
        if top is None:
            faults.append(f"{m['name']}: no spread on file")
            continue
        sp, cell, src = top
        lo = FLOOR_TIMES * sp
        hi = max(ALWAYS_ALLOWED, CEILING_TIMES * sp)
        ok = lo <= m["bound"] <= hi
        out(f"{m['name']:14s} bound {m['bound']:.3f} = {m['bound'] / sp:.2f} "
            f"times the widest spread on file, {100 * sp:.3f}% ({cell}, "
            f"{src}); window {lo:.4f} to {hi:.4f}"
            f"{'' if ok else '  OUTSIDE'}")
        if not ok:
            faults.append(f"{m['name']}: bound {m['bound']} outside "
                          f"{lo:.4f}..{hi:.4f}")
    return faults


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    faults = check(ap.parse_args(argv).root)
    for f in faults:
        print("FAULT:", f)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
