"""Hold ``BENCHMARK.json``'s bounds against the builder's own run sets
before the driver does.

    python3 -m perfbench.check_manifest --runs <dir> [--root <checkout>]

``<dir>`` holds one file per run, named ``<cell>.<set>.<seed>.log``,
whose last line is the run's result object; runs of one cell and set
form a set (six runs of the same code on different seeds). For every
end-to-end metric and cell it prints each set's median and spread (the
distance between the quartiles of ``statistics.quantiles(n=4)`` over
the median) and fails when a bound lies outside the window that the
driver's two refusals state:

  floor    "the runs of a workload that is new, or measured anew, may
           spread by at most 50% of a bound" (PR 22): taken against
           every set, untrimmed, which is stricter than the driver's
           mean of two sets without each set's farthest run;
  ceiling  "a bound may be at most 8 times the widest spread, or 1% if
           that is more" (PR 23): taken against the widest set over all
           the cells that report the metric, or the widest spread on
           file for them where that is wider (``perfbench/spreads/``:
           the driver's machines spread wider than one builder's lease,
           and it is the driver's reading that refuses).

Under each metric one more line says where the window would lie had
the driver drawn any two of the sets given (its own rule: tight by the
mean of two sets without each one's farthest run, loose by the wider of
two): advice for choosing, not a fault.

``setup_s`` is judged by the driver on its medians alone, so only its
limit of 10% and the drift between sets are checked. Between two sets
of the same code no median may differ by more than the bound. Also
fails on a static breach of the contract (``manifest.problems``), on a
run that was not correct or that failed a request, and on a metric
with fewer than two sets of three runs.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys

from . import manifest as manifest_mod
from . import spreads as spreads_mod
from . import stats

# the driver's window, stated once (perfbench/spreads.py)
FLOOR_SHARE = 1.0 / spreads_mod.FLOOR_TIMES
CEILING_TIMES = spreads_mod.CEILING_TIMES
ALWAYS_ALLOWED = spreads_mod.ALWAYS_ALLOWED


def read_runs(run_dir):
    """{cell: {set: [result, ...]}} from ``<cell>.<set>.<seed>.log``."""
    out = {}
    for fn in sorted(os.listdir(run_dir)):
        if not fn.endswith(".log"):
            continue
        cell, set_name, _seed = fn[:-4].rsplit(".", 2)
        with open(os.path.join(run_dir, fn), encoding="utf-8") as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.strip()]
        try:
            result = json.loads(lines[-1])
        except (IndexError, ValueError):
            result = None
        if not isinstance(result, dict) or "metrics" not in result:
            raise SystemExit(f"check_manifest: {fn} ends in no result object")
        out.setdefault(cell, {}).setdefault(set_name, []).append(result)
    return out


def check(root, run_dir, out=print):
    """Print the table; return the list of faults (empty: passes)."""
    faults = [f"manifest: {p}" for p in manifest_mod.problems(root)]
    man = manifest_mod.Manifest(root)
    runs = read_runs(run_dir)
    for cell, sets in runs.items():
        for set_name, results in sets.items():
            for r in results:
                if not r.get("correct") or r.get("failed"):
                    faults.append(f"{cell} set {set_name}: a run with correct="
                                  f"{r.get('correct')} failed={r.get('failed')}")
    for m in man.data["end_to_end"]:
        name, bound = m["name"], m["bound"]
        cells = [w["name"] for w in man.data["workloads"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        widest, measured, pair_lo, pair_hi = 0.0, False, None, 0.0
        for cell in cells:
            sets = {s: [r["metrics"][name]["value"] for r in rs
                        if name in r["metrics"]]
                    for s, rs in runs.get(cell, {}).items()}
            sets = {s: v for s, v in sets.items() if len(v) >= 3}
            if len(sets) < 2:
                out(f"{name:14s} {cell:38s} not measured: "
                    f"{len(sets)} set(s) of three runs or more")
                continue
            measured = True
            meds = {}
            spreads = {s: (stats.spread(v), stats.spread(stats.trimmed(v)))
                       for s, v in sets.items()}
            for s, vals in sorted(sets.items()):
                meds[s] = statistics.median(vals)
                sp, tsp = spreads[s]
                widest = max(widest, sp)
                verdict = ""
                if name != "setup_s" and sp > FLOOR_SHARE * bound:
                    verdict = "  TOO TIGHT: spread over half the bound"
                    faults.append(
                        f"{name} on {cell}, set {s}: spread {sp:.4f} is over "
                        f"{FLOOR_SHARE:.0%} of the bound {bound}")
                out(f"{name:14s} {cell:38s} set {s}: n={len(vals)} median "
                    f"{meds[s]:.6g} spread {100 * sp:.3f}% (without the "
                    f"farthest run {100 * tsp:.3f}%){verdict}")
            # the driver reads two sets: any two of these could be they
            for a, b in itertools.combinations(sorted(spreads), 2):
                lo = max(spreads[a][0], spreads[b][0])
                pair_lo = lo if pair_lo is None else min(pair_lo, lo)
                pair_hi = max(pair_hi, (spreads[a][1] + spreads[b][1]) / 2)
            worse = -1.0 if m["better"] == "higher" else 1.0
            names = sorted(meds)
            for a, b in zip(names, names[1:]):
                drift = (meds[b] - meds[a]) / abs(meds[a])
                if abs(drift) > bound and not (name == "setup_s"
                                               and worse * drift < 0):
                    faults.append(
                        f"{name} on {cell}: median of set {b} differs from "
                        f"set {a} by {drift:+.4f}, over the bound {bound}")
        if not measured:
            faults.append(f"{name}: no cell has two sets of runs")
            continue
        if name == "setup_s":
            out(f"{name:14s} bound {bound}: judged on medians only")
            continue
        filed = spreads_mod.widest(root, name, cells)
        if filed and filed[0] > widest:
            widest = filed[0]
            out(f"{name:14s} wider on file: {100 * widest:.3f}% "
                f"({filed[1]}, {filed[2]})")
        ceiling = max(ALWAYS_ALLOWED, CEILING_TIMES * widest)
        verdict = "inside the window"
        if bound > ceiling:
            verdict = "TOO LOOSE"
            faults.append(
                f"{name}: bound {bound} is over {CEILING_TIMES:g} times the "
                f"widest spread {widest:.4f} (ceiling {ceiling:.4f})")
        out(f"{name:14s} bound {bound}: floor {2 * widest:.4f} (twice the "
            f"widest spread) ceiling {ceiling:.4f} -> {verdict}")
        out(f"{name:14s} had the driver drawn any two of these sets: tight "
            f"under {2 * pair_hi:.4f}, loose over "
            f"{max(ALWAYS_ALLOWED, CEILING_TIMES * pair_lo):.4f}")
    return faults


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--runs", required=True)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args(argv)
    faults = check(args.root, args.runs)
    for f in faults:
        print("FAULT:", f)
    print("check_manifest:", "FAILED" if faults else "passed")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
