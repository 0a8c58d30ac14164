"""``prefill_rows_per_iter.*`` and ``prefill_programs_per_iter.*``: the
means of ``rows`` and ``programs`` over the window's ``engine.prefill``
spans that carry them (the iterations that enqueued a prefill program),
nothing from a program that does not write the args, and the served
tiny cells reading both end to end. Data only: the reader is
``span_arg_mean``, the synthetic rings are ``test_perfbench_spans``'s."""

import json

import pytest

import perfbench_tiny as tiny
import test_perfbench_spans as base

from perfbench import manifest, run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

SUFFIXES = {"chat": ("gpt3-1p3b.chat-open", "ttft_p75_ms"),
            "doc": ("gpt3-1p3b.doc-closed", "serve_tok_s"),
            "eva": ("evabyte-6p5b-cut.doc-bytes-closed", "serve_tok_s")}
KEYS = {"prefill_rows_per_iter": "rows",
        "prefill_programs_per_iter": "programs"}
NAMES = [f"{stem}.{suf}" for stem in KEYS for suf in SUFFIXES]
LAYER = "engine executables (serving.step, serving.prefill_chunk)"


def _with_prefill(events, rows_of):
    """Give every ``engine.prefill`` span for which ``rows_of(iter)`` is
    not None the two args, the programs at four rows each."""
    n = 0
    for e in events:
        if e["name"] != "engine.prefill":
            continue
        rows = rows_of(e["args"]["iter"])
        if rows is not None:
            e["args"].update(rows=rows, programs=-(-rows // 4))
            n += 1
    return n


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_data_in_the_order_it_was_added(name):
    """A file of arguments for the reader the benchmark has, and an
    entry of ``per_layer`` that lists its one cell; the six stand
    together in the order they were added, whatever later PRs append
    behind them."""
    real = manifest.Manifest(tiny.REPO)
    stem, suf = name.rsplit(".", 1)
    cell, moves = SUFFIXES[suf]
    mf = real.metric_file(name)
    assert mf["reader"] == "span_arg_mean"
    assert mf["args"] == {"trace": "engine", "span": "engine.prefill",
                          "key": KEYS[stem]}
    entry = next(m for m in real.data["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == LAYER
    assert entry["source"] == "program_counter"
    names = [m["name"] for m in real.data["per_layer"]]
    first = names.index(NAMES[0])
    assert names[first:first + len(NAMES)] == NAMES
    assert real.cell(cell)


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_over_the_iterations_that_enqueued_a_program(
        man, ring, name):
    events = base.steady(prefill=1.0)
    # every third iteration ran no prefill program: its span has no arg
    n = _with_prefill(events, lambda i: None if i % 3 == 0 else 1 + i % 7)
    live = [1 + i % 7 for i in range(40) if i % 3]
    assert n == len(live)
    # a program before the window's start does not count
    events.append(base.ev("engine.prefill", -50_000.0, 2.0, iter=-1,
                          rows=1000, programs=250))
    facts = ring(base.facts_for(events))
    want = live if "rows" in name else [-(-r // 4) for r in live]
    assert base.reading(man, name, facts) == pytest.approx(
        sum(want) / len(want))


@pytest.mark.parametrize("name", NAMES)
def test_a_prefill_span_without_the_counts_gives_nothing(man, ring, name):
    """The parent of the PR that added ``rows`` and ``programs``: every
    phase is there, and every chunk, the args are not."""
    facts = ring(base.facts_for(base.steady(prefill=1.0)))
    assert base.reading(man, name, facts) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_that_records_no_phases_gives_nothing(man, ring, name):
    events = base.requests() + [
        base.ev("serving.step", 10.0 * k, 8.0, active=2, step=k)
        for k in range(400)]
    assert base.reading(man, name, ring(base.facts_for(events))) is None


@pytest.mark.parametrize("name", NAMES)
def test_an_evicted_ring_gives_nothing(man, ring, name):
    events = [e for e in base.steady(n=200, period=20.0, prefill=1.0)
              if e["ts_ns"] > base.T0 * 1e9 + 30 * base.MS]
    _with_prefill(events, lambda i: 8)
    window = (base.T0 - 1.0, base.T1)
    facts = ring(base.facts_for(events, evicted=True, window=window))
    assert base.reading(man, name, facts) is None
    whole = ring(base.facts_for(events, evicted=False, window=window))
    assert base.reading(man, name, whole) == (8 if "rows" in name else 2)


@pytest.mark.parametrize("cell,suf", [("tiny-gpt.tiny-chat", "chat"),
                                      ("tiny-gpt.tiny-doc", "doc")])
def test_a_served_tiny_cell_reads_its_rows_and_programs(
        tmp_path, capsys, monkeypatch, cell, suf):
    monkeypatch.setattr(observe, "enable_compile_cache",
                        lambda: "off (tests)")
    root = tiny.make_root(str(tmp_path / "checkout"))
    run.main(["--workload", cell, "--seed", "2147484001", "--seconds", "7",
              "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    rows = res["metrics"][f"prefill_rows_per_iter.{suf}"]["value"]
    programs = res["metrics"][f"prefill_programs_per_iter.{suf}"]["value"]
    # four slots: (32, float32, 4) is four rows a program, so an
    # iteration that enqueues anything enqueues one program
    assert 1 <= rows <= 4 and programs == 1
    if suf == "doc":    # four clients, prompts of 2-5 chunks: rows share
        assert rows > 1.2
