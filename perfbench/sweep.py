"""Find an open-loop cell's knee once, on the chip: the same traffic
at several rates through one warm engine, tails and backlog at each.

    python3 -m perfbench.sweep --workload <name> --rates 1.5,2,2.5,3 --seconds 25 --seed 1

Not part of a benchmark run. PERF.md records what it printed and the
rate the cell was fixed at.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import run, stats
from .drivers import serve


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _, ctx = run.prepare(run.ROOT, args.workload, args.seed, args.seconds, 0)
    engine, _, _ = run.build_served(ctx)
    prog, vocab = ctx["program"], ctx["config"]["vocab_size"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        spec = dict(ctx["traffic"], rate_rps=rate)
        lives, (t0, t1) = serve.run_open(
            prog, engine, spec, args.seed + i, args.seconds, vocab,
            run.Tracer(False, 0, 0))
        recs = [r for r in serve.records(prog, lives, "open")
                if r["counted"]]
        ttft = [r["times"][0] - r["due"] for r in recs if r["times"]]
        third = max(1, len(ttft) // 3)
        itl = [g for r in recs for g in stats.gaps(r["times"])]
        print(json.dumps({
            "rate": rate, "counted": len(recs),
            "ok": sum(r["ok"] for r in recs),
            "finished_inside": sum(1 for r in recs
                                   if r["times"] and r["times"][-1] < t1),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "ttft_p50_first_third_ms": 1e3 * stats.percentile(ttft[:third], 50),
            "ttft_p50_last_third_ms": 1e3 * stats.percentile(ttft[-third:], 50),
            "itl_p50_ms": 1e3 * stats.percentile(itl, 50),
            "itl_p99_ms": 1e3 * stats.percentile(itl, 99),
            "queue_wait_p90_ms": 1e3 * stats.percentile(
                [r["queue_wait_s"] or 0.0 for r in recs], 90),
        }), flush=True)
    prog.free(engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())
