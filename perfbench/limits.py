"""Read, on the chip, the two numbers every limit of a cell is set
from: the largest value sound runs of the program give over many seeds,
and the smallest the lower-precision control gives.

    python3 -m perfbench.limits --workload <name> --seeds 1,2,3 --seconds <s> [--control 0]

One process runs every seed (set-up is most of a run). Not part of a
benchmark run; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from . import lowp, run


def control_values(ctx):
    """The control's value of every number the cell compares with the
    reference (not of exact counts)."""
    kind = ctx["traffic"]["kind"]
    ref, config, after = ctx["reference"], ctx["config"], ctx["after_check"]
    if kind == "train":
        import jax.numpy as jnp

        from .drivers import train

        ctrl = train.follow(ref, config, config["training"], after["spec"],
                            ctx["traffic"], ctx["seed"], config["vocab_size"],
                            jnp.dtype(config["dtype"]), mm=lowp.fp8_matmul)
        return {k: v for k, (v, _) in
                train.compare(ctrl, after["want"]).items()}
    from .drivers import serve

    gaps = serve.logit_gaps(ref, after["ref_params"], config, after["sample"],
                            chooser=lowp.int8_matmul)
    return {"served_logit_gap_max": max(gaps),
            "served_logit_gap_mean": sum(gaps) / len(gaps)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args(argv)
    sound, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        result, ctx = run.run_cell(run.ROOT, args.workload, seed,
                                   args.seconds, 0)
        row = {"seed": seed, "sound": ctx["checks"].values(),
               "attempted": result["attempted"], "failed": result["failed"],
               "end_to_end": {k: v["value"]
                              for k, v in result["metrics"].items()}}
        if args.control:
            row["control"] = control_values(ctx)
        print(json.dumps(row), flush=True)
        for k, v in row["sound"].items():
            sound[k] = max(sound.get(k, v), v)
        for k, v in row.get("control", {}).items():
            control[k] = min(control.get(k, v), v)
        del ctx, result
        gc.collect()
    print(json.dumps({"sound_largest": sound, "control_smallest": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
