"""BENCHMARK.json against the contract's static rules, and the rules
themselves against manifests that break them."""

import copy
import json
import os
import re
import shutil

import pytest

import perfbench_tiny as tiny

from perfbench import manifest

BENCH = manifest.load_json(os.path.join(tiny.REPO, "BENCHMARK.json"))
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_the_manifest_has_no_problems():
    assert manifest.problems(tiny.REPO) == []


def test_top_level_keys_are_exactly_the_contract_s():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench", "tests/perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", [x["name"] for g in (
    "configs", "workloads", "end_to_end", "per_layer") for x in BENCH[g]])
def test_every_name_is_letters_digits_and_three_signs(name):
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_unit_a_direction_a_source_and_a_reader(m):
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in manifest.SOURCES
    mf = manifest.load_json(os.path.join(
        tiny.REPO, "perfbench", "metrics", m["name"] + ".json"))
    assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
    assert os.path.isfile(os.path.join(
        tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_reported_wherever_this_one_is(m):
    assert m["moves"] in E2E
    mine = set(m.get("workloads", CELLS))
    assert mine <= set(E2E[m["moves"]].get("workloads", CELLS))
    assert mine <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_one_more_and_a_layer_metric(cell):
    man = manifest.Manifest(tiny.REPO)
    e2e = [m["name"] for m in man.metrics_of(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert man.metrics_of(cell, "per_layer")
    assert man.limits(cell) and man.traffic(man.cell(cell)["traffic"])


def test_bounds_and_four_chip_cells():
    assert all(0 < m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_metrics_of_one_layer_spell_it_the_same():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({re.sub(r"\W+", "", x).lower() for x in layers}) == len(layers)


def test_configuration_files_state_source_and_cuts():
    for c in BENCH["configs"]:
        f = manifest.load_json(os.path.join(tiny.REPO, c["file"]))
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert not any(re.search(r"(_dim|_rank|hidden_size|intermediate_size)$",
                                 k) for k in c["reduced"])
        for k in c["reduced"]:
            assert f[k] != f["published"][k]


def _broken(tmp_path, edit):
    root = str(tmp_path / "m")
    os.makedirs(root)
    shutil.copytree(os.path.join(tiny.REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = copy.deepcopy(BENCH)
    edit(bench)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return manifest.problems(root)


def _second_four_chip_cell(b):
    b["workloads"][0]["chips"] = b["workloads"][1]["chips"] = 4


def _moves_a_metric_the_cell_lacks(b):
    b["per_layer"][0]["moves"] = "train_tok_s"


BREACHES = {
    "name_with_a_space": lambda b: b["per_layer"][0].update(name="late p99"),
    "unit_with_a_space": lambda b: b["end_to_end"][0].update(
        unit="tokens per second"),
    "unit_too_long": lambda b: b["end_to_end"][0].update(unit="m" * 17),
    "greek_unit": lambda b: b["end_to_end"][0].update(unit="μs"),
    "bound_over_ten_percent": lambda b: b["end_to_end"][0].update(bound=0.2),
    "second_four_chip_cell": _second_four_chip_cell,
    "moves_no_end_to_end_metric": lambda b: b["per_layer"][0].update(
        moves="goodput"),
    "moves_a_metric_the_cell_lacks": _moves_a_metric_the_cell_lacks,
    "why_on_a_metric": lambda b: b["per_layer"][0].update(why="because"),
    "no_setup_s": lambda b: b["end_to_end"].pop(),
    "reduced_width": lambda b: b["configs"][1]["reduced"].append(
        "hidden_size"),
    "run_seconds_52": lambda b: b.update(run_seconds=52),
    "cell_without_traffic_file": lambda b: b["workloads"][0].update(
        traffic="nowhere"),
    "extra_top_level_key": lambda b: b.update(notes="x"),
    "metric_without_a_file": lambda b: b["per_layer"].append(dict(
        b["per_layer"][0], name="brand_new")),
    "configuration_used_by_no_cell": lambda b: b["configs"].append(dict(
        b["configs"][0], name="spare", file="perfbench/configs/spare.json")),
}


@pytest.mark.parametrize("edit", BREACHES.values(), ids=BREACHES.keys())
def test_a_breach_of_the_contract_is_found(tmp_path, edit):
    assert _broken(tmp_path, edit)
