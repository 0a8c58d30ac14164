#!/usr/bin/env python
"""Bounded-shard test runner driven by testslist.csv.

Parity: the reference encodes per-test timeouts and run types in
testslist.csv files consumed by tools/gen_ut_cmakelists.py, and
test/collective/README.md mandates serial execution for timing-sensitive
collective tests. Same contract here:

- ``testslist.csv`` rows: file, timeout (seconds), run_type
  (parallel | serial).
- parallel files are greedily balanced into N shards by timeout budget;
  each shard runs as one pytest invocation with a summed time bound.
- serial files (sockets, subprocess launches, wall-clock watchdogs) run
  one-per-invocation AFTER the parallel shards, never concurrently with
  anything.

Usage:
  python tests/run_shards.py --shards 4            # everything, bounded
  python tests/run_shards.py --shards 4 --shard 1  # one parallel shard
  python tests/run_shards.py --serial-only
  python tests/run_shards.py --list                # show the plan

Exit code is non-zero if any pytest invocation fails or exceeds its
budget. New test files must be added to testslist.csv — enforced by
test_manifest_complete in this directory's suite.
"""

from __future__ import annotations

import argparse
import csv
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "testslist.csv")

# --platform=tpu lane: a marked subset that runs on the REAL chip,
# sequentially (one device), with fp32 matmuls at full precision
# (conftest.py). Budgets are wall-clock seconds incl. compiles.
# Multi-device surfaces (shard_map, tp>1) stay on the virtual CPU mesh:
# the lane runs on one chip.
TPU_LANE = [
    # (file, timeout_s, extra_env)
    ("test_tpu_lane.py", 420, {}),
    ("test_flash_attention.py", 420, {}),
    ("test_ast_control_flow.py", 180, {}),
    ("test_generation.py", 600, {}),  # decode loops: many compiles
    ("test_offload.py", 420, {}),
    ("test_fused_projections.py", 420, {}),  # fused-vs-unfused on TPU numerics
    ("test_weight_only_quant.py", 420, {}),  # int8 dequant-fusion numerics
    # FULL schema output sweep on the chip, 8 sequential shards (round 5:
    # every schema's forward sees real-TPU numerics per float dtype —
    # reference op_test.py:2925 per-place discipline; ~345 s/shard cold,
    # fast on the persistent compile cache). Grad FD checks are sampled
    # (see the grad-policy note in test_op_schema_sweep.py).
    ("test_fused_conv.py", 420, {}),  # Pallas conv+BN on-chip numerics
    # flash-decode kernel, compiled (CPU interprets it)
    ("test_decode_attention.py", 420, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # paged KV serving: block-pool engine + paged flash-decode kernel,
    # compiled
    ("test_paged_kv.py", 420, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # request-lifecycle tracing: host-side by design, but the zero-
    # retrace-with-tracing-on and engine-lifecycle assertions deserve
    # one compiled run (device dispatch timing differs from CPU)
    ("test_tracing.py", 420, {}),
    # speculative decoding: bit-parity + one-compile draft/verify on the
    # paged kernel's q_len>1 bundle path; CPU-verified in the build
    # container — pair with benchmarks/bench_spec_decode.py for the
    # >=1.3x coupled-draft acceptance on chip
    ("test_spec_decode.py", 420, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # tree speculative decoding: the ancestor-masked bundle cell +
    # whole-tree verify in one kernel call; CPU-verified (interpret
    # mode) in the build container — this entry is the masked cell's
    # first compiled run (pair with bench_spec_decode.py's tree lanes
    # for the tree>=chain equal-budget acceptance on chip)
    ("test_spec_tree.py", 420, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # multi-replica router + chaos suite: host-side by design, but the
    # warmup-zero-compile, zero-retrace-on-survivors, and bit-identical
    # failover invariants deserve one compiled run (crash/drain timing
    # on a device differs from CPU; pair with benchmarks/bench_router.py
    # for the <2% router-overhead acceptance)
    ("test_router.py", 600, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # fleet observability plane: trace propagation / federation / SLO /
    # straggler detection are host-side, but the joined-trace and
    # zero-retrace-with-the-plane-on assertions deserve one compiled
    # run; the telemetry merge's fleet_obs block records the evidence
    # on BOTH lanes
    ("test_fleet_obs.py", 420, {}),
    # tensor-parallel serving: tp=2/4 bit-parity + one-compile + warmup
    # invariants need a multi-device mesh and the lane runs on one
    # chip, so this shard stays on the virtual CPU mesh (see header
    # note); tp=4 on four real chips is chip_smoke.py's job
    ("test_tp_serving.py", 600, {"PADDLE_TPU_TEST_PLATFORM": "cpu"}),
    # hierarchical KV tier: demote/readmit parity, the kill-mid-spill
    # matrix, and the disk-restart re-admission are host-side, but the
    # jitted demote/splice pair and the zero-retrace-with-tiering-on
    # invariant deserve one compiled run where device->host copies are
    # real DMAs; pair with benchmarks/bench_kv_tier.py for the >=80%
    # recompute-elimination acceptance
    ("test_kv_tier.py", 600, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # self-healing supervisor: warm restart / quarantine / brownout are
    # host-side by design, but the zero-retrace-after-rebuild-warmup and
    # bit-identical-replay-of-innocents invariants deserve one compiled
    # run (a fresh engine's warmup compiles against the REAL backend and
    # crash/restart timing differs from CPU); pair with
    # benchmarks/bench_overload.py for the <2% supervisor-overhead and
    # >=80% controlled-goodput acceptances
    ("test_supervisor.py", 600, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # perf observability: on chip the peak table resolves from the real
    # device_kind, so MFU/roofline go from "unknown" to classified —
    # this entry is the first run where the ledger publishes real MFU
    # (CPU verifies capture mechanics + honesty contracts only)
    ("test_perf.py", 420, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # quantized serving: int8/fp8 KV pools (dequant in the paged kernel
    # prologue) + weight-only Pallas quant matmul; CPU-interpret-verified
    # in the build container — this entry is the quantized kernels' first
    # compiled run (pair with benchmarks/bench_paged_kv.py kv_format_ab
    # for the >=1.8x fixed-budget capacity and bench_quant_matmul.py)
    ("test_quantization_serving.py", 420,
     {"PADDLE_TPU_FLASH_DECODE": "1", "PADDLE_TPU_QUANT_WEIGHTS": "1"}),
    *[(f"test_op_schema_sweep.py", 600,
       {"PADDLE_TPU_SWEEP_SHARD": f"{i}/8"}) for i in range(8)],
    # sampled FD-grad lane (every 16th schema incl. grads): a compile
    # and a host sync per FD evaluation — generous budget
    ("test_op_schema_sweep.py", 900, {"PADDLE_TPU_SWEEP_STRIDE": "16"}),
]

# Documented CPU-vs-TPU tolerance deltas the on-chip lane runs under.
# Written into benchmarks/tpu_lane_results.json with every lane run so
# the "full sweep on the real chip" claim is auditable (per-shard rc +
# wall time) instead of builder-attested.
TPU_TOLERANCE_DELTAS = [
    {"where": "flash_attention / flash_attn_varlen",
     "delta": "bf16-only on chip (fp32 operands fail Mosaic compilation — "
              "the MXU path is half-precision operands with f32 "
              "accumulation); CPU lane sweeps fp32 in interpret mode",
     "source": "tests/test_op_schema_sweep.py _TPU_HALF_ONLY"},
    {"where": "fused_conv_bn_train / fused_conv_bn_eval",
     "delta": "bf16-only on chip, same MXU contract as flash attention",
     "source": "tests/test_op_schema_sweep.py _TPU_HALF_ONLY"},
    {"where": "flash_decode_attention / paged_flash_decode_attention",
     "delta": "the schema sweep runs bf16 only on chip (production "
              "dtype; fp32 swept on CPU in interpret mode); the kernels' "
              "own files (tests/test_decode_attention.py, test_paged_kv.py, "
              "test_spec_tree.py) run their fp32 cases compiled, at "
              "matmul precision 'highest'",
     "source": "tests/test_op_schema_sweep.py _TPU_HALF_ONLY"},
    {"where": "flash_decode_attention_int8 / paged_flash_decode_attention_"
              "int8 / quant_matmul",
     "delta": "the schema sweep runs bf16 activations only on chip "
              "(int8/fp8 storage + bf16 compute is the production "
              "pairing); 16-row int8/fp8 pool blocks compile on Mosaic "
              "as they are (tests/test_quantization_serving.py)",
     "source": "tests/test_op_schema_sweep.py _TPU_HALF_ONLY"},
    {"where": "power_to_db",
     "delta": "5e-4 vs the CPU 1e-5 oracle tolerance (TPU log/pow "
              "transcendental rounding)",
     "source": "COVERAGE.md round-5 notes"},
    {"where": "fp32 matmul ops (whole sweep)",
     "delta": "run with jax_default_matmul_precision=highest — TPU fp32 "
              "dots otherwise default to a bf16-class mode (~1e-2 error) "
              "that would void the 1e-5 oracle comparisons",
     "source": "tests/conftest.py"},
]


def load_manifest():
    rows = []
    with open(MANIFEST) as f:
        for row in csv.DictReader(f):
            rows.append({"file": row["file"], "timeout": int(row["timeout"]),
                         "run_type": row["run_type"].strip()})
    return rows


def partition(rows, n_shards):
    """Greedy longest-first balancing by timeout budget."""
    shards = [[] for _ in range(n_shards)]
    budgets = [0] * n_shards
    for row in sorted(rows, key=lambda r: -r["timeout"]):
        i = budgets.index(min(budgets))
        shards[i].append(row)
        budgets[i] += row["timeout"]
    return shards, budgets


def merge_dispatch_records(dump_prefix):
    """Cross-shard schema enforcement: union the per-process dispatch
    records the conftest dumped and diff against the registries (each
    pytest process already enforces its own record at sessionfinish;
    this re-checks the union and cleans up)."""
    import glob

    root = os.path.dirname(HERE)
    if root not in sys.path:  # launched as `python tests/run_shards.py`
        sys.path.insert(0, root)
    import paddle_tpu  # noqa: F401
    from paddle_tpu.ops.schemas import SCHEMAS
    from paddle_tpu.ops.schemas_extended import (DYNAMIC_DISPATCH,
                                                 NO_SCHEMA_WHITE_LIST)

    names = set()
    for path in glob.glob(dump_prefix + ".*"):
        with open(path) as fh:
            names |= {ln.strip() for ln in fh if ln.strip()}
        os.remove(path)
    strays = {n for n in names
              if n not in SCHEMAS and n not in NO_SCHEMA_WHITE_LIST
              and n not in DYNAMIC_DISPATCH["enumerated"]
              and not n.startswith(DYNAMIC_DISPATCH["prefixes"])}
    if strays:
        print(f"[run_shards] dispatch enforcement: {len(strays)} op(s) "
              f"ran without schema/white-list: {sorted(strays)}",
              flush=True)
        return 1
    print(f"[run_shards] dispatch enforcement: {len(names)} recorded op "
          "names all covered", flush=True)
    return 0


def setup_telemetry_dump() -> str:
    """Point every shard process's conftest at a per-pid observability
    snapshot dump; stale dumps from an interrupted run are cleared so
    they can't leak into this run's merge."""
    import glob

    prefix = os.path.join(HERE, ".telemetry_snap")
    os.environ["PADDLE_TPU_TELEMETRY_DUMP"] = prefix
    for stale in glob.glob(prefix + ".*.json"):
        os.remove(stale)
    return prefix


def _summarize_snapshot(snap: dict) -> dict:
    """Reduce one shard's observability snapshot to the lane-relevant
    aggregates (fused-conv dispatch outcomes, compile counts/seconds,
    retraces, step records, trace span counts + serving latency
    digests)."""
    fams = snap.get("metrics", {})

    def series(name):
        return fams.get(name, {}).get("samples", [])

    def digest(name):
        for s in series(name):
            if "quantiles" in s:
                return {**{f"p{round(float(q) * 100)}": v
                           for q, v in s["quantiles"].items()},
                        "count": s.get("count", 0)}
        return None

    digests = {short: d for short, name in (
        ("ttft_s", "paddle_tpu_serving_ttft_summary_seconds"),
        ("tpot_s", "paddle_tpu_serving_tpot_summary_seconds"),
        ("queue_wait_s", "paddle_tpu_serving_queue_wait_seconds"),
        ("prefill_chunk_s", "paddle_tpu_serving_prefill_chunk_seconds"),
    ) if (d := digest(name)) is not None and d["count"]}

    # the perf ledger's lane-relevant columns: per-entry static
    # flops/bytes + roofline class + achieved rates (entries don't sum
    # across shards; the merge keeps the busiest shard's row per entry)
    perf_entries = {}
    for entry, row in (snap.get("perf", {}).get("ledger", {}) or {}).items():
        perf_entries[entry] = {
            k: row.get(k) for k in (
                "flops", "bytes_accessed", "temp_bytes",
                "arithmetic_intensity", "roofline", "mfu", "hbm_bw_util",
                "calls", "items", "items_per_s", "bytes_per_item")}

    # fleet observability plane (router federation / SLO / stragglers):
    # per-shard evidence the plane ran — scrape outcomes, federated
    # series high-water mark, per-objective SLO verdicts + burn rates,
    # straggler flag transitions
    fleet_obs = {
        "scrapes": {"/".join(s["labels"].values()) or "total": int(s["value"])
                    for s in series("paddle_tpu_fleet_scrapes_total")},
        "federated_series": int(max(
            (s["value"] for s in series("paddle_tpu_fleet_federated_series")),
            default=0)),
        "slo_ok": {s["labels"].get("objective", "?"): bool(s["value"])
                   for s in series("paddle_tpu_slo_ok")},
        "slo_burn": {"/".join(s["labels"].values()): round(float(s["value"]),
                                                           4)
                     for s in series("paddle_tpu_slo_burn_rate")},
        "stragglers_total": int(sum(
            s["value"] for s in series("paddle_tpu_router_stragglers_total"))),
    }

    return {
        "trace_spans": dict(snap.get("tracing", {}).get("span_counts", {})),
        "serving_digests": digests,
        "fleet_obs": fleet_obs,
        "perf_entries": perf_entries,
        # pt-analysis CI trend lines: findings by rule + suppression
        # accounting (recorded by the self-clean test's analyzer run)
        "analysis_findings": {
            "/".join(s["labels"].values()): int(s["value"])
            for s in series("paddle_tpu_analysis_findings_total")},
        "analysis_suppressions": {
            **{"used/" + "/".join(s["labels"].values()): int(s["value"])
               for s in series(
                   "paddle_tpu_analysis_suppressions_used_total")},
            **{"unused/" + "/".join(s["labels"].values()): int(s["value"])
               for s in series(
                   "paddle_tpu_analysis_suppressions_unused_total")}},
        "fused_conv_dispatch": {
            "/".join(s["labels"].values()): int(s["value"])
            for s in series("paddle_tpu_fused_conv_dispatch_total")},
        "flash_decode_dispatch": {
            **{"hit/" + "/".join(s["labels"].values()): int(s["value"])
               for s in series("paddle_tpu_flash_decode_hits_total")},
            **{"fallback/" + "/".join(s["labels"].values()): int(s["value"])
               for s in series("paddle_tpu_flash_decode_fallbacks_total")}},
        "compiles_total": int(sum(
            s["value"] for s in series("paddle_tpu_compiles_total"))),
        "compile_seconds_total": round(sum(
            s.get("sum", 0.0)
            for s in series("paddle_tpu_compile_seconds")), 2),
        "retraces_total": int(sum(
            s["value"] for s in series("paddle_tpu_retraces_total"))),
        "nan_check_trips": int(sum(
            s["value"] for s in series("paddle_tpu_nan_check_trips_total"))),
        "steps_recorded": len(snap.get("steps", [])),
    }


def build_perf_ledger_block(bench_dir: str, perf_entries: dict) -> tuple:
    """The telemetry lane's ``perf_ledger`` block: the merged per-entry
    roofline rows + the regression-gate verdict against the committed
    ``benchmarks/perf_baseline.json``. Returns (block, rc) — rc is 1
    when any pinned metric regressed past its tolerance (the loud
    failure the gate exists for)."""
    root = os.path.dirname(HERE)
    if root not in sys.path:
        sys.path.insert(0, root)
    from paddle_tpu.observability import perf as _perf

    fresh = _perf.collect_bench_metrics(bench_dir)
    baseline = _perf.load_baseline(
        os.path.join(bench_dir, "perf_baseline.json"))
    verdict = _perf.compare_to_baseline(fresh, baseline)
    block = {"entries": perf_entries, "bench_metrics": fresh,
             "baseline_gate": verdict}
    if verdict.get("failures"):
        print("[run_shards] PERF REGRESSION GATE FAILED:", flush=True)
        for f in verdict["failures"]:
            print(f"[run_shards]   {f['metric']}: fresh {f['fresh']} vs "
                  f"baseline {f['baseline']} (tol {f['rel_tol']:.0%}, "
                  f"bound {f['bound']:.4g}, delta {f['delta_pct']}%)",
                  flush=True)
        print("[run_shards]   a real improvement? re-run the bench "
              "best-of-3 and update benchmarks/perf_baseline.json with "
              "the new number in the same commit", flush=True)
        return block, 1
    print(f"[run_shards] perf gate: {verdict.get('checked', 0)} metrics "
          f"within tolerance ({len(verdict.get('skipped', []))} skipped)",
          flush=True)
    return block, 0


def merge_telemetry_snapshots(dump_prefix: str, platform: str) -> tuple:
    """Merge the per-shard snapshots into benchmarks/telemetry_lane.json
    (next to tpu_lane_results.json): per-shard summaries plus summed
    totals, so the chip lane's fused-conv hit rate and compile counts
    are auditable without re-running anything. Also evaluates the
    perf-regression gate; returns (path, gate_rc)."""
    import datetime
    import glob
    import json

    shards = []
    totals: dict = {"fused_conv_dispatch": {}, "flash_decode_dispatch": {},
                    "trace_spans": {}, "serving_digests": {},
                    "fleet_obs": {"scrapes": {}, "federated_series": 0,
                                  "slo_ok": {}, "slo_burn": {},
                                  "stragglers_total": 0},
                    "analysis_findings": {}, "analysis_suppressions": {},
                    "perf_entries": {},
                    "compiles_total": 0,
                    "compile_seconds_total": 0.0, "retraces_total": 0,
                    "nan_check_trips": 0, "steps_recorded": 0}
    for path in sorted(glob.glob(dump_prefix + ".*.json")):
        try:
            with open(path) as fh:
                snap = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        summary = _summarize_snapshot(snap)
        summary["pid"] = path.rsplit(".", 2)[-2]
        shards.append(summary)
        for fam in ("fused_conv_dispatch", "flash_decode_dispatch",
                    "trace_spans", "analysis_findings",
                    "analysis_suppressions"):
            for k, v in summary[fam].items():
                totals[fam][k] = totals[fam].get(k, 0) + v
        # percentiles don't sum: keep the busiest shard's digest per
        # latency (the serving suite runs in one shard anyway)
        for k, d in summary["serving_digests"].items():
            if d["count"] > totals["serving_digests"].get(
                    k, {"count": 0})["count"]:
                totals["serving_digests"][k] = d
        # fleet plane: sum scrape/straggler counters, keep the
        # high-water federated-series mark, AND the SLO verdicts (a
        # breach in ANY shard is a lane breach), keep the WORST burn
        # rate per objective/window
        fo, tfo = summary["fleet_obs"], totals["fleet_obs"]
        for k, v in fo["scrapes"].items():
            tfo["scrapes"][k] = tfo["scrapes"].get(k, 0) + v
        tfo["federated_series"] = max(tfo["federated_series"],
                                      fo["federated_series"])
        for obj, ok in fo["slo_ok"].items():
            tfo["slo_ok"][obj] = tfo["slo_ok"].get(obj, True) and ok
        for k, burn in fo["slo_burn"].items():
            tfo["slo_burn"][k] = max(tfo["slo_burn"].get(k, 0.0), burn)
        tfo["stragglers_total"] += fo["stragglers_total"]
        # ledger rows don't sum either: per entry, keep the shard that
        # called it most (its timing window is the representative one)
        for entry, row in summary["perf_entries"].items():
            cur = totals["perf_entries"].get(entry)
            if cur is None or (row.get("calls") or 0) > (cur.get("calls")
                                                         or 0):
                totals["perf_entries"][entry] = row
        for k in ("compiles_total", "compile_seconds_total",
                  "retraces_total", "nan_check_trips", "steps_recorded"):
            totals[k] += summary[k]
        os.remove(path)
    totals["compile_seconds_total"] = round(totals["compile_seconds_total"], 2)
    hits = sum(v for k, v in totals["fused_conv_dispatch"].items()
               if k.startswith("hit/"))
    falls = sum(v for k, v in totals["fused_conv_dispatch"].items()
                if k.startswith("fallback/"))
    totals["fused_conv_hit_rate"] = (
        round(hits / (hits + falls), 4) if hits + falls else None)
    # the cross-process join in one line: router-side lanes
    # (router.request/router.attempt) next to the replica-side request
    # spans they propagate into — nonzero on both sides means joined
    # traces were actually exercised this lane (CPU and TPU alike)
    totals["fleet_obs"]["joined_trace_spans"] = {
        name: totals["trace_spans"].get(name, 0)
        for name in ("router.request", "router.attempt", "request")}
    # fold the most recent serving bench artifact (if any) into the lane
    # so one file carries the full telemetry story: compile counts,
    # fused-conv hit rate, AND the continuous-batching numbers
    def _read_bench(fname):
        p = os.path.join(os.path.dirname(HERE), "benchmarks", fname)
        if not os.path.exists(p):
            return None
        try:
            with open(p) as fh:
                return json.load(fh)
        except (OSError, json.JSONDecodeError):
            return None

    serving_bench = _read_bench("bench_serving.json")
    checkpoint_bench = _read_bench("bench_checkpoint.json")
    decode_bench = _read_bench("bench_decode.json")
    paged_kv_bench = _read_bench("bench_paged_kv.json")
    spec_decode_bench = _read_bench("bench_spec_decode.json")
    quant_bench = _read_bench("bench_quant.json")
    router_bench = _read_bench("bench_router.json")
    tp_bench = _read_bench("bench_tp.json")
    kv_tier_bench = _read_bench("bench_kv_tier.json")
    overload_bench = _read_bench("bench_overload.json")
    bench_dir = os.path.join(os.path.dirname(HERE), "benchmarks")
    perf_ledger, gate_rc = build_perf_ledger_block(
        bench_dir, totals.pop("perf_entries"))
    out_path = os.path.join(bench_dir, "telemetry_lane.json")
    with open(out_path, "w") as fh:
        json.dump({
            "platform": platform,
            "finished": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "totals": totals,
            "perf_ledger": perf_ledger,
            "shards": shards,
            "serving_bench": serving_bench,
            "checkpoint_bench": checkpoint_bench,
            "decode_bench": decode_bench,
            "paged_kv_bench": paged_kv_bench,
            "spec_decode_bench": spec_decode_bench,
            "quant_bench": quant_bench,
            "router_bench": router_bench,
            "tp_bench": tp_bench,
            "kv_tier_bench": kv_tier_bench,
            "overload_bench": overload_bench,
        }, fh, indent=1)
    print(f"[run_shards] telemetry lane -> {out_path} "
          f"(compiles {totals['compiles_total']}, fused-conv hit rate "
          f"{totals['fused_conv_hit_rate']}, perf gate rc={gate_rc})",
          flush=True)
    return out_path, gate_rc


def run_static_analysis(label: str) -> int:
    """The pt-analysis CI gate: analyze the files git reports changed
    (text mode, exact rule ids + fix hints on stdout). Runs in BOTH
    lanes before any pytest shard — a trace-safety/PRNG/lock/Pallas
    regression fails fast, without waiting out a full shard budget. The
    full-tree self-clean gate is tests/test_analysis.py."""
    cmd = [sys.executable, "-m", "paddle_tpu.analysis", "--changed-only"]
    print(f"[run_shards] static analysis ({label}): {' '.join(cmd)}",
          flush=True)
    try:
        proc = subprocess.run(cmd, timeout=300, cwd=os.path.dirname(HERE))
        return proc.returncode
    except subprocess.TimeoutExpired:
        print("[run_shards] static analysis EXCEEDED its 300s budget",
              flush=True)
        return 124


def run_pytest(files, budget, label, extra_env=None):
    cmd = [sys.executable, "-m", "pytest", "-q", "--no-header",
           *(os.path.join(HERE, f) for f in files)]
    print(f"[run_shards] {label}: {len(files)} files, budget {budget}s",
          flush=True)
    env = None
    if extra_env:
        env = {**os.environ, **extra_env}
    try:
        proc = subprocess.run(cmd, timeout=budget, cwd=os.path.dirname(HERE),
                              env=env)
        return proc.returncode
    except subprocess.TimeoutExpired:
        print(f"[run_shards] {label} EXCEEDED its {budget}s budget", flush=True)
        return 124


def run_tpu_lane(slack: float, only=()) -> int:
    """Run the on-chip lane (``only``: just these files of it) and write
    benchmarks/tpu_lane_results.json (per-shard rc, wall time, and the
    documented tolerance-delta list) so the on-chip sweep claim is
    auditable, not builder-attested."""
    import datetime
    import json

    unknown = set(only) - {f for f, _, _ in TPU_LANE}
    if unknown:
        raise SystemExit(f"not in the TPU lane: {sorted(unknown)}")
    tdump = setup_telemetry_dump()
    rc = run_static_analysis("tpu lane")
    shards = []
    for f, timeout, extra in TPU_LANE:
        if only and f not in only:
            continue
        t0 = time.monotonic()
        shard_rc = run_pytest([f], int(timeout * slack), f"tpu-lane {f}",
                              extra_env={"PADDLE_TPU_TEST_PLATFORM": "tpu",
                                         **extra})
        shards.append({"file": f, "extra_env": extra, "rc": shard_rc,
                       "wall_s": round(time.monotonic() - t0, 1),
                       "budget_s": int(timeout * slack)})
        rc |= shard_rc
    out = {
        "platform": "tpu",
        "finished": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "overall_rc": rc,
        "shards": shards,
        "tolerance_deltas": TPU_TOLERANCE_DELTAS,
    }
    path = os.path.join(os.path.dirname(HERE), "benchmarks",
                        "tpu_lane_results.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"[run_shards] tpu lane results -> {path} (rc={rc})", flush=True)
    _, gate_rc = merge_telemetry_snapshots(tdump, "tpu")
    return rc | gate_rc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--shard", type=int, default=None,
                    help="run only this parallel shard index")
    ap.add_argument("--serial-only", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--slack", type=float, default=1.5,
                    help="budget multiplier over summed timeouts")
    ap.add_argument("--enforce-dispatch", action="store_true",
                    help="merge per-shard dispatch records and fail on "
                         "ops without schema/white-list coverage")
    ap.add_argument("--platform", choices=("cpu", "tpu"), default="cpu",
                    help="tpu: run the marked on-chip lane instead of "
                         "the CPU shards")
    ap.add_argument("--only", nargs="+", default=(), metavar="FILE",
                    help="with --platform=tpu: run just these lane files")
    args = ap.parse_args(argv)

    if args.platform == "tpu":
        return run_tpu_lane(args.slack, args.only)

    if args.enforce_dispatch:
        import glob

        os.environ["PADDLE_TPU_DISPATCH_DUMP"] = os.path.join(
            HERE, ".dispatch_record")
        # stale dumps from an interrupted previous run would be merged
        # into this run's enforcement — clear them up front
        for stale in glob.glob(os.environ["PADDLE_TPU_DISPATCH_DUMP"] + ".*"):
            os.remove(stale)

    tdump = setup_telemetry_dump()
    rows = load_manifest()
    par = [r for r in rows if r["run_type"] == "parallel"]
    ser = [r for r in rows if r["run_type"] == "serial"]
    shards, budgets = partition(par, args.shards)

    if args.list:
        for i, (sh, b) in enumerate(zip(shards, budgets)):
            print(f"shard {i} (budget {b}s): "
                  + " ".join(r["file"] for r in sh))
        print("serial: " + " ".join(r["file"] for r in ser))
        return 0

    rc = run_static_analysis("cpu lane")
    if not args.serial_only:
        targets = range(args.shards) if args.shard is None else [args.shard]
        for i in targets:
            files = [r["file"] for r in shards[i]]
            if not files:
                continue
            budget = int(budgets[i] * args.slack)
            rc |= run_pytest(files, budget, f"shard {i}")
    if args.shard is None or args.serial_only:
        for r in ser:
            rc |= run_pytest([r["file"]], int(r["timeout"] * args.slack),
                             f"serial {r['file']}")
    if args.enforce_dispatch:
        rc |= merge_dispatch_records(os.environ["PADDLE_TPU_DISPATCH_DUMP"])
    _, gate_rc = merge_telemetry_snapshots(tdump, "cpu")
    return rc | gate_rc


if __name__ == "__main__":
    sys.exit(main())
