"""Reading ``BENCHMARK.json`` and the files it names, and checking them
against the contract. Used by the harness, by ``check_manifest.py`` and
by the tests; imports no jax."""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MAX_BOUND = 0.1


def load_json(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


class Manifest:
    """``BENCHMARK.json`` of the checkout at ``root`` plus the per-name
    files under ``perfbench/``."""

    def __init__(self, root):
        self.root = os.path.abspath(root)
        self.data = load_json(os.path.join(self.root, "BENCHMARK.json"))
        self.dir = os.path.join(self.root, "perfbench")

    def cell(self, name):
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json")

    def config(self, name):
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise SystemExit(f"perfbench: no configuration {name!r}")

    def traffic(self, name):
        return load_json(os.path.join(self.dir, "traffic", name + ".json"))

    def limits(self, cell_name):
        return load_json(os.path.join(self.dir, "limits", cell_name + ".json"))

    def metric_file(self, name):
        return load_json(os.path.join(self.dir, "metrics", name + ".json"))

    def reader(self, name):
        """The ``read`` function of ``perfbench/readers/<name>.py`` of
        this checkout, loaded by path so that a reader added beside the
        others is found without touching a file that is there."""
        path = os.path.join(self.dir, "readers", name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"perfbench_reader_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def metrics_of(self, cell_name, group):
        """Entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those that list it, and those that list no cells."""
        return [m for m in self.data[group]
                if cell_name in m.get("workloads", [cell_name])]

    def read_metrics(self, cell_name, group, facts):
        """name -> {"value", "unit"} for every metric of the group whose
        reader finds something to read in ``facts``."""
        out = {}
        for m in self.metrics_of(cell_name, group):
            mf = self.metric_file(m["name"])
            value = self.reader(mf["reader"])(facts, **mf.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


def problems(root):
    """Every breach of the contract's static rules found in the
    manifest at ``root``, as sentences; empty when there is none."""
    out = []
    try:
        man = Manifest(root)
    except (OSError, ValueError) as e:
        return [f"BENCHMARK.json cannot be read: {e}"]
    d = man.data
    if set(d) != TOP_KEYS:
        out.append(f"top-level keys are {sorted(d)}, want {sorted(TOP_KEYS)}")
        return out
    if os.path.getsize(os.path.join(man.root, "BENCHMARK.json")) > 64 * 1024:
        out.append("BENCHMARK.json is over 64 KiB")
    rs = d["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        out.append(f"run_seconds {rs!r} is no whole number from 1 to 51")
    n_cells = len(d["workloads"])
    if (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 > 43200:
        out.append("a full check of 24 cells at this run_seconds does not fit")
    if not 1 <= n_cells <= 24:
        out.append(f"{n_cells} workloads")

    def line(s, what):
        if not (isinstance(s, str) and 1 <= len(s) <= 200
                and "\n" not in s and "\t" not in s):
            out.append(f"{what} is not one line of 1 to 200 characters")

    for w in d["command"]:
        line(w, "a word of command")
        if w.startswith("/") or ".." in w.split("/"):
            out.append(f"command word {w!r} leaves the repo")
    for p in d["paths"]:
        if not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p):
            out.append(f"path {p!r}")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in d["paths"])

    names = lambda xs: [x.get("name") for x in xs]  # noqa: E731
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(d[group])
        for n in ns:
            if not (isinstance(n, str) and NAME.match(n)):
                out.append(f"{group} name {n!r} is not a name")
        if len(set(ns)) != len(ns):
            out.append(f"{group} has a name twice")
    if set(names(d["end_to_end"])) & set(names(d["per_layer"])):
        out.append("a metric is both end-to-end and per-layer")

    cfg_names = set(names(d["configs"]))
    used, files = set(), set()
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"configuration {c.get('name')} has keys {sorted(c)}")
            continue
        line(c["source"], f"source of {c['name']}")
        line(c["why"], f"why of {c['name']}")
        if not under_paths(c["file"]) or c["file"] in files:
            out.append(f"file of {c['name']} is outside paths or used twice")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(man.root, c["file"])):
            out.append(f"file of {c['name']} is missing")
        if len(c["reduced"]) > 16:
            out.append(f"{c['name']} reduces over 16 keys")
        for k in c["reduced"]:
            if not NAME.match(k):
                out.append(f"reduced key {k!r} is not a name")
            if re.search(r"(_dim|_rank|hidden_size|intermediate_size|head_dim"
                         r"|num_experts_per_tok)$", k):
                out.append(f"{c['name']} reduces the width {k}")
    pairs = set()
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')} has keys {sorted(w)}")
            continue
        line(w["why"], f"why of {w['name']}")
        if w["config"] not in cfg_names:
            out.append(f"{w['name']} names no configuration")
        if not NAME.match(str(w["traffic"])):
            out.append(f"traffic {w['traffic']!r} is not a name")
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']} asks for {w['chips']} chips")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"{w['name']} repeats a pair")
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        for kind, ext in (("traffic", w["traffic"]), ("limits", w["name"])):
            if not os.path.isfile(os.path.join(man.dir, kind, ext + ".json")):
                out.append(f"{w['name']}: no perfbench/{kind}/{ext}.json")
    if cfg_names - used:
        out.append(f"configurations used by no cell: {sorted(cfg_names - used)}")
    four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
    if four > max(1, n_cells // 4):
        out.append(f"{four} four-chip cells of {n_cells}")

    cells = set(names(d["workloads"]))
    e2e = {m["name"]: m for m in d["end_to_end"] if "name" in m}
    if "setup_s" not in e2e:
        out.append("no setup_s among the end-to-end metrics")

    def reported_by(m):
        return set(m.get("workloads", cells))

    for m in d["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or not {"name", "unit", "better",
                                               "bound", "source"} <= set(m):
            out.append(f"end-to-end {m.get('name')} has keys {sorted(m)}")
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: source {m['source']}")
        if not (isinstance(m["bound"], float) and 0 < m["bound"] <= MAX_BOUND):
            out.append(f"{m['name']}: bound {m['bound']!r}")
    for m in d["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} or not {
                "name", "unit", "better", "source", "layer",
                "moves"} <= set(m):
            out.append(f"per-layer {m.get('name')} has keys {sorted(m)}")
            continue
        line(m["layer"], f"layer of {m['name']}")
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: source {m['source']}")
        if m["moves"] not in e2e:
            out.append(f"{m['name']} moves {m['moves']}, no end-to-end metric")
        elif not reported_by(m) <= reported_by(e2e[m["moves"]]):
            out.append(f"{m['name']} is reported where {m['moves']} is not")
    for m in d["end_to_end"] + d["per_layer"]:
        if not UNIT.match(str(m.get("unit"))):
            out.append(f"{m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"{m.get('name')}: better {m.get('better')!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"{m.get('name')} lists the unknown cell {w}")
        path = os.path.join(man.dir, "metrics", str(m.get("name")) + ".json")
        if not os.path.isfile(path):
            out.append(f"{m.get('name')}: no perfbench/metrics file")
            continue
        mf = load_json(path)
        if not os.path.isfile(os.path.join(man.dir, "readers",
                                           str(mf.get("reader")) + ".py")):
            out.append(f"{m['name']}: no reader {mf.get('reader')!r}")
    for c in cells:
        mine = [m["name"] for m in d["end_to_end"] if c in reported_by(m)]
        if "setup_s" not in mine or len(mine) < 2:
            out.append(f"{c} reports {mine}: needs setup_s and one more")
        if not any(c in reported_by(m) for m in d["per_layer"]):
            out.append(f"{c} reports no per-layer metric")
    return out
