"""check_manifest.py against recorded run sets: it must reject the
bounds PR 22 and PR 23 were refused for, and pass a bound that lies
inside the window."""

import copy
import json
import os
import shutil
import statistics

import pytest

import perfbench_tiny as tiny

from perfbench import check_manifest, manifest

DATA = manifest.load_json(os.path.join(os.path.dirname(__file__), "data",
                                       "recorded_sets.json"))
BENCH = manifest.load_json(os.path.join(tiny.REPO, "BENCHMARK.json"))


def checkout(tmp_path, case, extra_bounds=None):
    """A checkout whose manifest gives the case's metric the case's
    bound, and a directory of run files, one per run, for that cell.
    The recorded sets are the only spreads it holds: the real cells'
    files of spreads stay behind."""
    root, runs = str(tmp_path / "checkout"), str(tmp_path / "runs")
    os.makedirs(root)
    os.makedirs(runs)
    shutil.copytree(os.path.join(tiny.REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "spreads"))
    bench = copy.deepcopy(BENCH)
    cell = case["cell"]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] == cell]
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] == bench["workloads"][0]["config"]]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if cell in m.get("workloads", [cell]):
                if "workloads" in m:
                    m["workloads"] = [cell]
                kept.append(m)
        bench[group] = kept
    if case["metric"] not in [m["name"] for m in bench["end_to_end"]]:
        # PR 22's metric is a per-layer one since PR 24: put it back the
        # way a later PR adds an end-to-end metric, by an entry and a file
        bench["end_to_end"].insert(0, {
            "name": case["metric"], "unit": "ms", "better": "lower",
            "bound": case["bound"], "source": "host_clock",
            "workloads": [cell]})
        with open(os.path.join(root, "perfbench", "metrics",
                               case["metric"] + ".json"), "w") as fh:
            json.dump({"name": case["metric"], "unit": "ms",
                       "reader": "latency_percentile",
                       "args": {"what": "ttft", "q": 90}}, fh)
    others = {m["name"]: m["bound"] for m in bench["end_to_end"]
              if m["name"] not in (case["metric"], "setup_s")}
    for m in bench["end_to_end"]:
        if m["name"] == case["metric"]:
            m["bound"] = case["bound"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    for set_name, values in case["sets"].items():
        for i, v in enumerate(values):
            metrics = {case["metric"]: {"value": v, "unit": "x"},
                       "setup_s": {"value": 50.0 + 0.1 * i, "unit": "s"}}
            for o, b in others.items():  # a third of its bound: inside
                metrics[o] = {"value": 77.0 * (1 + b / 6 * (i % 3 - 1)),
                              "unit": "x"}
            line = {"correct": True, "attempted": 112, "failed": 0,
                    "metrics": metrics,
                    "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1, "memory_peak_bytes": 9e9}}
            with open(os.path.join(
                    runs, f"{cell}.{set_name}.{3000000001 + i}.log"), "w") as fh:
                fh.write("[perfbench] earlier lines\n" + json.dumps(line) + "\n")
    return root, runs


def faults_of(tmp_path, case):
    root, runs = checkout(tmp_path, case)
    lines = []
    return check_manifest.check(root, runs, out=lines.append), lines


def test_the_recorded_pr22_sets_spread_as_the_ledger_says():
    for s, want in DATA["pr22"]["want_spreads_ms"].items():
        q1, _, q3 = statistics.quantiles(DATA["pr22"]["sets"][s], n=4)
        assert q3 - q1 == pytest.approx(want, abs=1e-3)
        assert statistics.median(DATA["pr22"]["sets"][s]) \
            == pytest.approx(1020.96)


def test_pr22_s_bound_is_rejected_as_too_tight(tmp_path):
    faults, lines = faults_of(tmp_path, DATA["pr22"])
    assert any("ttft_p90_ms" in f and "over 50% of the bound" in f
               for f in faults)
    assert any("TOO TIGHT" in ln for ln in lines)
    assert not any("widest spread" in f for f in faults)


def test_pr23_s_bound_is_rejected_as_too_loose(tmp_path):
    faults, lines = faults_of(tmp_path, DATA["pr23"])
    assert any("serve_tok_s" in f and "widest spread" in f for f in faults)
    assert any("TOO LOOSE" in ln for ln in lines)
    assert not any("over 50% of the bound" in f for f in faults)


def test_pr23_would_have_passed_with_that_bound_inside_the_window(tmp_path):
    case = dict(DATA["pr23"], bound=0.03)
    faults, lines = faults_of(tmp_path, case)
    assert faults == []
    assert any("inside the window" in ln for ln in lines)


def test_a_wider_spread_on_file_lifts_the_ceiling_and_not_the_floor(tmp_path):
    """PR 23's bound again, with a file that says the driver read the
    cell three times as wide as these sets: no longer too loose. The
    floor is still held against the sets that were run."""
    case = DATA["pr23"]
    root, runs = checkout(tmp_path, case)
    lines = []
    assert any("widest spread" in f for f in
               check_manifest.check(root, runs, out=lines.append))
    own = max(statistics.quantiles(v, n=4)[2] - statistics.quantiles(v, n=4)[0]
              for v in case["sets"].values()) / statistics.median(
                  case["sets"]["a"])
    os.makedirs(os.path.join(root, "perfbench", "spreads"))
    with open(os.path.join(root, "perfbench", "spreads",
                           case["cell"] + ".json"), "w") as fh:
        json.dump({"ledger": {case["metric"]: [
            {"pr": 99, "spread": case["bound"] / 7.0}]}}, fh)
    assert case["bound"] / 7.0 > own
    lines = []
    assert check_manifest.check(root, runs, out=lines.append) == []
    assert any("wider on file" in ln and "ledger PR 99" in ln for ln in lines)
    tight = dict(DATA["pr22"])
    root2, runs2 = checkout(tmp_path / "two", tight)
    os.makedirs(os.path.join(root2, "perfbench", "spreads"))
    with open(os.path.join(root2, "perfbench", "spreads",
                           tight["cell"] + ".json"), "w") as fh:
        json.dump({"ledger": {tight["metric"]: [
            {"pr": 99, "spread": 0.0001}]}}, fh)
    assert any("over 50% of the bound" in f for f in
               check_manifest.check(root2, runs2, out=lambda s: None))


def test_one_percent_is_never_too_loose(tmp_path):
    case = dict(DATA["pr23"], bound=0.01)
    case["sets"] = {s: [1880.0 + 0.1 * i for i in range(6)]
                    for s in ("a", "b")}
    assert faults_of(tmp_path, case)[0] == []


def test_a_bound_inside_the_window_passes_and_prints_every_set(tmp_path):
    faults, lines = faults_of(tmp_path, DATA["sound"])
    assert faults == []
    table = [ln for ln in lines if ln.startswith("ttft_p90_ms") and ": n=" in ln]
    assert len(table) == 2 and all("median" in ln and "spread" in ln
                                   for ln in table)


def test_drift_between_sets_of_the_same_code_is_a_fault(tmp_path):
    case = copy.deepcopy(DATA["sound"])
    case["sets"]["b"] = [v * 1.08 for v in case["sets"]["b"]]
    faults, _ = faults_of(tmp_path, case)
    assert any("median of set b differs" in f for f in faults)


def test_an_incorrect_run_is_a_fault(tmp_path):
    root, runs = checkout(tmp_path, DATA["sound"])
    fn = sorted(os.listdir(runs))[0]
    with open(os.path.join(runs, fn)) as fh:
        line = json.loads(fh.read().splitlines()[-1])
    line["correct"] = False
    with open(os.path.join(runs, fn), "w") as fh:
        fh.write(json.dumps(line) + "\n")
    assert any("correct=False" in f
               for f in check_manifest.check(root, runs, out=lambda s: None))


def test_one_set_is_not_a_measurement(tmp_path):
    case = copy.deepcopy(DATA["sound"])
    case["sets"].pop("b")
    faults, lines = faults_of(tmp_path, case)
    assert any("no cell has two sets" in f for f in faults)
    assert any("not measured" in ln for ln in lines)


def test_the_command_line_exits_non_zero_on_a_fault(tmp_path, capsys):
    root, runs = checkout(tmp_path, DATA["pr22"])
    assert check_manifest.main(["--runs", runs, "--root", root]) == 1
    out = capsys.readouterr().out
    assert "FAULT:" in out and out.strip().endswith("check_manifest: FAILED")
