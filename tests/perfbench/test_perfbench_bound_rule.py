"""The rule the bounds of ``BENCHMARK.json`` follow, held against the
spreads on file (``perfbench/spreads/<cell>.json``; ``PERF.md`` section 2
quotes them): a bound is at least twice and at most eight times the
widest spread its cells show, the builder's sets and the driver's own
(or 1%); a served bound is five times it rounded up to a whole per
cent, or the ceiling above which a cell is to be steadied and not its
bound widened, whichever is less; and every set the builder ran spreads
by 40% of the bound or less."""

import math
import os

import pytest

import perfbench_tiny as tiny

from perfbench import manifest, spreads, stats

BENCH = manifest.load_json(os.path.join(tiny.REPO, "BENCHMARK.json"))
BOUNDED = [m for m in BENCH["end_to_end"] if "workloads" in m]
PAIRS = [(m["name"], c) for m in BOUNDED for c in m["workloads"]]
# ISSUE 35: above these a cell is steadied, its bound is not widened
CEILINGS = {"ttft_p75_ms": 0.10, "itl_p99_ms": 0.10, "serve_tok_s": 0.05}
PR24 = {"ttft_p75_ms": 0.06, "itl_p99_ms": 0.08, "serve_tok_s": 0.01,
        "train_tok_s": 0.01}


def bound(name):
    return next(m["bound"] for m in BOUNDED if m["name"] == name)


def widest(name):
    cells = next(m["workloads"] for m in BOUNDED if m["name"] == name)
    return spreads.widest(tiny.REPO, name, cells)[0]


def test_every_bounded_metric_is_one_the_rule_knows():
    assert {m["name"] for m in BOUNDED} == set(PR24)
    assert {m["name"] for m in BENCH["end_to_end"]} - set(PR24) == {"setup_s"}


@pytest.mark.parametrize("name,cell", PAIRS)
def test_each_cell_has_its_spreads_on_file(name, cell):
    got = spreads.on_file(tiny.REPO, cell).get(name, {})
    assert got and all(0 < sp < 0.5 for sp in got.values())
    # the builder's side and the driver's side both, where both exist
    assert any(k.startswith(("set ", "quoted ")) for k in got)


@pytest.mark.parametrize("name", sorted(PR24))
def test_a_bound_lies_inside_the_drivers_window(name):
    """At least twice the widest spread on file (a cell's runs may
    spread by half a bound) and at most eight times it, or 1%."""
    w = widest(name)
    assert spreads.FLOOR_TIMES * w <= bound(name) \
        <= max(spreads.ALWAYS_ALLOWED, spreads.CEILING_TIMES * w)


def test_the_tool_says_the_same():
    lines = []
    assert spreads.check(tiny.REPO, out=lines.append) == []
    assert len(lines) == len(BOUNDED)


@pytest.mark.parametrize("name", sorted(CEILINGS))
def test_a_served_bound_is_five_times_the_widest_spread_or_the_ceiling(name):
    """Five times the wider source (the contract's multiple), rounded
    up to a whole per cent, and the ceiling where that would pass it:
    the cell is then one to steady (``PERF.md`` section 7 names it)."""
    five = math.ceil(100 * 5 * widest(name) - 1e-9) / 100
    assert bound(name) == min(five, CEILINGS[name])


@pytest.mark.parametrize("name", ["serve_tok_s", "train_tok_s", "ttft_p75_ms"])
def test_only_a_bound_that_sat_at_the_windows_edge_fell(name):
    """``itl_p99_ms`` went from PR 24's 8% to 6%: 8% was 6.85 times the
    widest spread on file, of the eight the driver allows. No other
    bound is under the one PR 24 gave it."""
    assert bound(name) >= PR24[name]
    assert bound("itl_p99_ms") < PR24["itl_p99_ms"] \
        < spreads.CEILING_TIMES * widest("itl_p99_ms")


@pytest.mark.parametrize("name,cell", [p for p in PAIRS if p[0] in CEILINGS])
def test_every_set_the_builder_ran_spreads_by_two_fifths_of_the_bound(
        name, cell):
    data = manifest.load_json(os.path.join(
        tiny.REPO, "perfbench", "spreads", cell + ".json"))
    sets = data["sets"][name]
    assert len(sets) >= 2 and all(len(v) >= 6 for v in sets.values())
    for values in sets.values():
        assert stats.spread(values) <= 0.4 * bound(name)


def test_a_file_of_a_cell_nobody_has_reads_as_nothing(tmp_path):
    assert spreads.on_file(str(tmp_path), "no-such.cell") == {}
    assert spreads.widest(str(tmp_path), "serve_tok_s", ["no-such.cell"]) \
        is None


def test_a_bound_outside_the_window_is_a_fault(tmp_path):
    """A checkout whose one served bound is a tenth of the spread on
    file, then a hundred times it: both are told."""
    import json
    import shutil

    root = tiny.make_root(str(tmp_path / "checkout"))
    filed = os.path.join(root, "perfbench", "spreads")
    shutil.rmtree(filed)   # the real cells' files: the tiny cells have none
    os.makedirs(filed)
    bench = manifest.load_json(os.path.join(root, "BENCHMARK.json"))
    for m in bench["end_to_end"]:
        for cell in m.get("workloads", []):
            path = os.path.join(filed, cell + ".json")
            data = manifest.load_json(path) if os.path.isfile(path) else {
                "sets": {}}
            data["sets"][m["name"]] = {"a": [100, 101, 102, 103, 104, 105]}
            with open(path, "w") as fh:
                json.dump(data, fh)
    sp = stats.spread([100, 101, 102, 103, 104, 105])
    for factor, faults in ((0.1, True), (3.0, False), (100.0, True)):
        for m in bench["end_to_end"]:
            if "workloads" in m:
                m["bound"] = factor * sp
        with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
            json.dump(bench, fh)
        assert bool(spreads.check(root, out=lambda _: None)) is faults
