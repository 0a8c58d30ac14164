"""Run one cell of the benchmark once.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: refuses to start unless jax finds a TPU with the chips
the cell asks for, makes weights and inputs from ``--seed``, warms up,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON object as its last line (its
last key, ``checks``, and standard error's last lines hold each number
compared beside its limit). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read in a run that also takes a
profiler trace of a few seconds. Everything else worth reading goes on
earlier lines. A crash is a crash: no handler turns it into a key.

The cell, its configuration, its traffic, its limits, every metric and
every reader are files found by the names in ``BENCHMARK.json``; there
is no branch here on any of those names.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from . import manifest as manifest_mod  # noqa: E402
from . import trace_reduce  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _age_at_import():
    """Seconds this process had lived when this module was imported,
    from /proc (0 where there is none)."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_IMPORT = _age_at_import()


def process_age(at):
    """Age of the process at ``at`` (a ``perf_counter`` reading)."""
    return _AGE_AT_IMPORT + (at - _T_IMPORT)


def say(msg):
    print(f"[perfbench] {msg}", flush=True)


class BackendCompiles:
    """Counts programs XLA really compiled (persistent-cache misses),
    from jax's own monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = 0
        monitoring.register_event_listener(self._on)

    def _on(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def misses(self):
        return self.requests - self.hits


class Tracer:
    """A profiler trace of ``length_s`` seconds inside the window (a
    served cell's last seconds, a training cell's from ``after_s`` on);
    off when ``on`` is false. ``t0`` and ``t1`` are read on the host's
    clock inside two marks that land on the trace's host plane, which is
    how ``trace_reduce`` cuts the device's events to the same stretch."""

    def __init__(self, on, after_s, length_s):
        self.on, self.after_s, self.length_s = on, after_s, length_s
        self.dir = self.t0 = self.t1 = None
        self._thread = None

    def _start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_OPEN):
            self.t0 = time.perf_counter()

    def _stop(self):
        import jax

        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SHUT):
            self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def window_start(self, t_end=None):
        """Called by a driver as its window opens. With the time the
        window will shut (a served cell): trace its last ``length_s``
        seconds from a thread beside the sender. Stopping a trace holds
        the interpreter for some tenths of a second, which stalls the
        sender and the engine's loop alike; stopped as the window shuts,
        that falls outside it."""
        if not self.on or t_end is None:
            return

        def body():
            time.sleep(max(0.0, t_end - self.length_s - time.perf_counter()))
            self._start()
            time.sleep(max(0.0, t_end - time.perf_counter()))
            self._stop()

        self._thread = threading.Thread(target=body, name="perfbench-trace")
        self._thread.start()

    def between_steps(self, elapsed):
        """For a training cell: start and stop at step boundaries."""
        if not self.on:
            return
        if self.t0 is None and elapsed >= self.after_s:
            self._start()
        elif self.t0 is not None and self.t1 is None \
                and time.perf_counter() - self.t0 >= self.length_s:
            self._stop()

    def finish(self):
        """Wait for the trace; return what it reduced to, or None."""
        if not self.on:
            return None
        if self._thread is not None:
            self._thread.join()
        if self.t0 is not None and self.t1 is None:
            self._stop()
        if self.dir is None:
            return None
        try:
            red = trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        # ``window_s`` is the trace's own; the readers that count the
        # clients' tokens take the same stretch on the host's clock
        red["host_window"] = (self.t0, self.t1)
        cut = ("between the marks" if red["window_marked"]
               else "NO MARKS: the whole trace")
        say(f"trace: busy {red['busy_s']:.4f}s of {red['window_s']:.4f}s "
            f"({cut}; host clock {self.t1 - self.t0:.4f}s; busy over the "
            f"whole trace {red['busy_whole_trace_s']:.4f}s)")
        return red


def memory_peak():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def bytes_in_use():
    import jax

    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.devices()))


class Checks:
    """Each number compared, printed beside its limit."""

    def __init__(self, limits):
        self.limits, self.rows = limits, []

    def add(self, name, value, note=""):
        limit = self.limits.get(name, -1)  # no limit set: cannot pass
        ok = value <= limit
        self.rows.append((name, value, limit, ok))
        say(f"check {name}: {value:.6g} (limit {limit:g}) "
            f"{'ok' if ok else 'FAILED'} {note}")

    @property
    def correct(self):
        return bool(self.rows) and all(r[3] for r in self.rows)

    def values(self):
        return {r[0]: r[1] for r in self.rows}

    def beside_limits(self):
        """{name: {"value": .., "limit": ..}} of every number compared,
        for the result's line (a value that is no finite number, as
        where nothing finished, by its name: JSON has none for it)."""
        return {name: {"value": value if math.isfinite(value) else str(value),
                       "limit": limit}
                for name, value, limit, _ in self.rows}


def build_served(ctx):
    """Weights from the seed, the engine around them, warm and started.
    Returns (engine, parameter spec, weight dtype)."""
    import jax
    import jax.numpy as jnp

    from . import weights

    config, prog = ctx["config"], ctx["program"]
    spec = ctx["reference"].param_spec(config)
    dtype = jnp.dtype(config["dtype"])
    t = [time.perf_counter()]
    leaves = weights.make(spec, ctx["seed"], dtype)
    jax.block_until_ready(leaves)
    t.append(time.perf_counter())
    engine = prog.build(config, spec, leaves, ctx["chips"])
    t.append(time.perf_counter())
    warm = engine.warmup()
    engine.start()
    t.append(time.perf_counter())
    say(f"engine warm: {warm['compiles']} executables; process age "
        f"{process_age(t[3]):.1f}s of which weights {t[1] - t[0]:.1f}s, "
        f"engine {t[2] - t[1]:.1f}s, warmup {t[3] - t[2]:.1f}s")
    return engine, spec, dtype


def serve_flow(ctx):
    import jax.numpy as jnp

    from . import stats, weights
    from .drivers import serve
    from .programs import observe

    config, tr, seed, prog, ref = (ctx["config"], ctx["traffic"], ctx["seed"],
                                   ctx["program"], ctx["reference"])
    engine, spec, dtype = build_served(ctx)
    facts = ctx["facts"]
    facts["backend_compiles_setup"] = ctx["backend"].misses
    before = observe.counters()
    # engine counters are read outside the lead-in and the window:
    # ``stats()`` holds the engine for up to 0.7 s, which read as a late
    # generator and a long gap when it was called at the window's edges
    engine_start = prog.counters(engine)
    loop = serve.run_open if tr["kind"] == "open" else serve.run_closed
    lives, window = loop(prog, engine, tr, seed, ctx["seconds"],
                         config["vocab_size"], ctx["tracer"])
    engine_end = prog.counters(engine)
    if tr["kind"] == "closed":
        n_open, waited = serve.await_first_tokens(lives)
        say(f"waited {waited:.2f}s past the window for the first token of "
            f"{n_open} request(s) still prefilling")
    recs = serve.records(prog, lives, tr["kind"])
    after = observe.counters()
    facts.update(
        window=window, requests=recs, setup_s=process_age(window[0]),
        engine_start=engine_start, engine_end=engine_end,
        compiles_in_window=after["compiles"] - before["compiles"],
        flash_decode_fallbacks=after["flash_decode_fallbacks"],
        flash_decode_hits=after["flash_decode_hits"],
        backend_compiles_window=ctx["backend"].misses
        - facts["backend_compiles_setup"],
        memory_peak_bytes=memory_peak())
    facts["trace"] = ctx["tracer"].finish()
    if tr["kind"] == "closed":
        from .readers import serve_tokens_per_s

        say(f"tokens/s by 3 s of the window: "
            f"{serve_tokens_per_s.by_slice(facts)}")
    prog.free(engine)
    del engine, lives
    say(f"program freed: {bytes_in_use() / 1e9:.2f} GB still in use")

    final = [r for r in recs if r["counted"] and r["final"]]
    failed = [r for r in final if not r["ok"]]
    firsts = [r for r in final if r["times"]]
    late = sorted(r["sent"] - r["due"] for r in final) or [0.0]
    say(f"{len(firsts)} first tokens, {stats.samples_beyond(len(firsts), 90)} "
        f"beyond the 90th percentile; ttft p50 "
        f"{_pct_ms(firsts, 50):.1f} ms, p90 {_pct_ms(firsts, 90):.1f} ms; "
        f"{sum(max(0, len(r['times']) - 1) for r in final)} gaps; sent "
        f"at most {late[-1] * 1e3:.2f} ms late")
    checks = Checks(ctx["limits"])
    checks.add("token_count_mismatches", float(len(failed)),
               "every counted request returns exactly its output length")
    sample = serve.sample_for_check(final, int(tr["check_sample"]), seed)
    t0 = time.perf_counter()
    ref_params = weights.make(spec, seed, dtype, upcast=jnp.float32)
    gaps = serve.logit_gaps(ref, ref_params, config, sample)
    say(f"reference over {len(sample)} requests, {len(gaps)} served tokens, "
        f"in {time.perf_counter() - t0:.1f}s")
    # with nothing finished there is nothing to hold against a limit
    checks.add("served_logit_gap_max", max(gaps, default=float("inf")),
               "widest gap of a served token below the reference's best")
    checks.add("served_logit_gap_mean",
               sum(gaps) / len(gaps) if gaps else float("inf"),
               "mean gap over the sample")
    ctx["after_check"] = dict(ref_params=ref_params, sample=sample, spec=spec)
    return len(final), len(failed), checks


def _pct_ms(recs, q):
    from . import stats

    vals = [(r["times"][0] - r["due"]) * 1e3 for r in recs]
    return stats.percentile(vals, q) if vals else float("nan")


def train_flow(ctx):
    import jax
    import jax.numpy as jnp

    from . import weights
    from .drivers import train
    from .programs import observe

    config, tr, seed, prog, ref = (ctx["config"], ctx["traffic"], ctx["seed"],
                                   ctx["program"], ctx["reference"])
    spec = ref.param_spec(config)
    dtype = jnp.dtype(config["dtype"])
    hyper = config["training"]
    vocab = config["vocab_size"]
    leaves = weights.make(spec, seed, dtype)
    trainer = prog.Trainer(config, tr, spec, leaves, ctx["chips"])
    got = train.first_steps(trainer, spec, tr, seed, vocab, dtype,
                            hyper["beta1"])
    say(f"first steps: losses {got['losses']}; process age "
        f"{process_age(time.perf_counter()):.1f}s")
    facts = ctx["facts"]
    facts["backend_compiles_setup"] = ctx["backend"].misses
    before = observe.counters()
    ends, losses, t0 = train.window(
        trainer, tr, seed, vocab, ctx["seconds"], int(tr["follow_steps"]),
        ctx["tracer"])
    after = observe.counters()
    facts.update(
        window=(t0, ends[-1]), step_ends=ends, setup_s=process_age(t0),
        tokens_per_step=int(tr["batch"]) * int(tr["seq"]),
        compiles_in_window=after["compiles"] - before["compiles"],
        backend_compiles_window=ctx["backend"].misses
        - facts["backend_compiles_setup"],
        memory_peak_bytes=memory_peak())
    facts["trace"] = ctx["tracer"].finish()
    trainer.free()
    del trainer
    say(f"program freed: {bytes_in_use() / 1e9:.2f} GB still in use")

    checks = Checks(ctx["limits"])
    checks.add("nonfinite_losses_in_window", float(train.nonfinite(losses)),
               f"{len(losses)} steps")
    t1 = time.perf_counter()
    want = train.follow(ref, config, hyper, spec, tr, seed, vocab, dtype)
    say(f"reference followed {len(want['losses'])} steps in "
        f"{time.perf_counter() - t1:.1f}s")
    for name, (value, note) in train.compare(got, want).items():
        checks.add(name, value, note)
    ctx["after_check"] = dict(want=want, spec=spec)
    return len(losses), train.nonfinite(losses), checks


FLOWS = {"open": serve_flow, "closed": serve_flow, "train": train_flow}


def prepare(root, workload, seed, seconds, trace, on_chip=True):
    """Find the cell's files, look for the chip, and gather what a flow
    needs. ``on_chip=False`` (the tests) skips the look for a chip and
    the table of peaks."""
    man = manifest_mod.Manifest(root)
    cell = man.cell(workload)
    config = man.config(cell["config"])
    tr = man.traffic(cell["traffic"])
    import jax

    devs = jax.devices()
    if on_chip and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        raise SystemExit(
            f"perfbench: {workload} needs {cell['chips']} TPU chip(s); jax "
            f"found {len(devs)} device(s) of platform {devs[0].platform!r}")
    peaks = None
    if on_chip:
        table = manifest_mod.load_json(os.path.join(man.dir, "peaks.json"))
        if devs[0].device_kind not in table:
            raise SystemExit(f"perfbench: no peaks for {devs[0].device_kind!r}")
        peaks = table[devs[0].device_kind]
    from .programs import observe

    cache_dir = observe.enable_compile_cache()
    say(f"{workload} seed {seed} on {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}); compile cache {cache_dir}")
    facts = {"cell": cell, "config": config, "traffic": tr, "peaks": peaks,
             "chips": cell["chips"], "kind": tr["kind"]}
    return man, {
        "config": config, "traffic": tr, "seed": seed, "seconds": seconds,
        "chips": cell["chips"], "limits": man.limits(workload),
        "facts": facts, "backend": BackendCompiles(),
        "tracer": Tracer(bool(trace), 1.0, float(tr["trace_window_s"])),
        "program": importlib.import_module(
            f"perfbench.programs.{config['program']}"),
        "reference": importlib.import_module(
            f"perfbench.references.{config['reference']}"),
    }


def run_cell(root, workload, seed, seconds, trace, on_chip=True):
    """Everything of a run but the argument parsing."""
    import jax

    man, ctx = prepare(root, workload, seed, seconds, trace, on_chip)
    facts, tr, devs = ctx["facts"], ctx["traffic"], jax.devices()
    attempted, failed, checks = FLOWS[tr["kind"]](ctx)
    ctx["checks"] = checks
    group = "per_layer" if trace else "end_to_end"
    for name, m in man.read_metrics(workload, "end_to_end", facts).items():
        say(f"{name}: {m['value']!r} {m['unit']}")
    metrics = man.read_metrics(workload, group, facts)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": facts["memory_peak_bytes"]}
    result = {"correct": checks.correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    red = facts.get("trace")
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    result["checks"] = checks.beside_limits()  # last in the line
    ctx["result"] = result
    return result, ctx


def main(argv=None, root=ROOT, on_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, _ = run_cell(root, args.workload, args.seed, args.seconds,
                         args.trace, on_chip)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    # each number compared beside its limit: standard error's last lines
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
