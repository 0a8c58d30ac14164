"""The DeepSeek-V2 cell's prefill chunk, swept once on the chip: the
cell's own traffic through the engine at several ``prefill_chunk``
(under 171 tokens a chunk attends absorbed through the paged kernel, from
171 on decompressed in XLA: ``generation.latent_absorb_below``), each
for ``--seconds`` after the lead-in, with no reference check. Prints
one JSON line a chunk: tokens/s as ``serve_tok_s`` counts them, requests
finished, the engine's counters and the memory peak. Not part of a
benchmark run; PERF.md records what it printed.

    chiprun -- python3 benchmarks/dsv2_chunk_sweep.py --chunks 64,256,1024 --seconds 40
"""
import argparse
import gc
import json
import sys

sys.path.insert(0, ".")
from perfbench import run  # noqa: E402
from perfbench.drivers import serve  # noqa: E402
from perfbench.readers import serve_tokens_per_s  # noqa: E402

CELL = "deepseek-v2-cut.longdoc-closed"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2147483931)
    ap.add_argument("--blocks", type=int, default=0)
    args = ap.parse_args()
    for chunk in (int(c) for c in args.chunks.split(",")):
        _, ctx = run.prepare(run.ROOT, CELL, args.seed, args.seconds, 0)
        ctx["config"]["serving"]["prefill_chunk"] = chunk
        if args.blocks:
            ctx["config"]["serving"]["num_blocks"] = args.blocks
        prog, tr = ctx["program"], ctx["traffic"]
        try:
            engine, _, _ = run.build_served(ctx)
            lives, window = serve.run_closed(
                prog, engine, tr, args.seed, args.seconds,
                ctx["config"]["vocab_size"], ctx["tracer"])
            counters = prog.counters(engine)
            recs = serve.records(prog, lives, "closed")
            facts = {"window": window, "requests": recs}
            done = [r for r in recs if r["counted"] and r["final"]]
            print(json.dumps({
                "prefill_chunk": chunk, "rows": engine._chunk_rows,
                "serve_tok_s": serve_tokens_per_s.read(facts),
                "by_3s": serve_tokens_per_s.by_slice(facts),
                "finished": len(done),
                "finished_ok": sum(r["ok"] for r in done),
                "first_tokens": sum(1 for r in recs if r["times"]),
                "counters": {k: v for k, v in counters.items()
                             if k != "prefix_cache"},
                "memory_peak_gb": run.memory_peak() / 1e9}), flush=True)
            prog.free(engine)
            del engine, lives
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"prefill_chunk": chunk,
                              "error": repr(e)[:400]}), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
