"""``prefill_fill_rows_per_iter.chat`` and ``.doc``: the mean of ``fill``
(the rows of an iteration that were a slot's second or later chunk: the
last prefill program's spare rows) over the window's ``engine.prefill``
spans that carry it, nothing from a program that does not write the arg
(the parent of the PR that added it writes ``rows`` and ``programs``
alone), and the served tiny cells reading it end to end. Data only: the
reader is ``span_arg_mean``, the synthetic rings are
``test_perfbench_spans``'s. No ``.eva`` twin: that cell's program has
one row, so the arg is there and always 0."""

import json

import pytest

import perfbench_tiny as tiny
import test_perfbench_prefill_rows as rows_base
import test_perfbench_spans as base

from perfbench import manifest, run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

SUFFIXES = {"chat": ("gpt3-1p3b.chat-open", "ttft_p75_ms"),
            "doc": ("gpt3-1p3b.doc-closed", "serve_tok_s")}
NAMES = [f"prefill_fill_rows_per_iter.{suf}" for suf in SUFFIXES]
LAYER = "serving entry (serving/engine.py scheduler, block pool)"


def _with_fill(events, fill_of):
    """Give every ``engine.prefill`` span for which ``fill_of(iter)`` is
    not None the three args of an iteration that enqueued a program:
    one first chunk and ``fill`` further ones, four rows a program."""
    n = 0
    for e in events:
        if e["name"] != "engine.prefill":
            continue
        fill = fill_of(e["args"]["iter"])
        if fill is not None:
            e["args"].update(rows=1 + fill, programs=1, fill=fill)
            n += 1
    return n


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_data_beside_the_accepted_ones(name):
    """A file of arguments for the reader the benchmark has, and an
    entry of ``per_layer`` that lists its one cell, put after every
    entry that was there."""
    real = manifest.Manifest(tiny.REPO)
    cell, moves = SUFFIXES[name.rsplit(".", 1)[1]]
    mf = real.metric_file(name)
    assert mf["reader"] == "span_arg_mean"
    assert mf["args"] == {"trace": "engine", "span": "engine.prefill",
                          "key": "fill"}
    names = [m["name"] for m in real.data["per_layer"]]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == LAYER
    assert entry["layer"] in {m["layer"] for m in real.data["per_layer"]
                              if m["name"] not in NAMES}
    assert (entry["unit"], entry["better"], entry["source"]) \
        == ("rows", "higher", "program_counter")
    assert names.index(name) > max(names.index(n) for n in rows_base.NAMES)
    assert real.cell(cell)


@pytest.mark.parametrize("name", rows_base.NAMES)
def test_the_rows_and_programs_entries_stand_where_they_stood(name):
    """The six entries of ``test_perfbench_prefill_rows`` are
    consecutive, in the order they were added, and the two of this file
    come behind them in theirs; what a later PR appends is its own."""
    real = manifest.Manifest(tiny.REPO)
    names = [m["name"] for m in real.data["per_layer"]]
    first = names.index(rows_base.NAMES[0])
    assert names[first:first + 6] == rows_base.NAMES
    assert [n for n in names[first + 6:] if n in NAMES] == NAMES
    stem, suf = name.rsplit(".", 1)
    cell, moves = rows_base.SUFFIXES[suf]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == real.metric_file(name)["layer"] \
        == rows_base.LAYER
    assert real.metric_file(name)["args"]["key"] == rows_base.KEYS[stem]


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_over_the_iterations_that_enqueued_a_program(
        man, ring, name):
    events = base.steady(prefill=1.0)
    # every third iteration ran no prefill program: its span has no arg;
    # one in seven of the others had no spare row to give, which counts
    n = _with_fill(events, lambda i: None if i % 3 == 0 else i % 7)
    live = [i % 7 for i in range(40) if i % 3]
    assert n == len(live) and 0 in live
    # a program before the window's start does not count
    events.append(base.ev("engine.prefill", -50_000.0, 2.0, iter=-1,
                          rows=8, programs=1, fill=7000))
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(
        sum(live) / len(live))


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_prefill_span_gives_nothing(man, ring, name):
    """The parent writes ``rows`` and ``programs`` and no ``fill``: the
    two older metrics read, this one has nothing to read and the line
    leaves it out."""
    events = base.steady(prefill=1.0)
    rows_base._with_prefill(events, lambda i: 1 + i % 4)
    facts = ring(base.facts_for(events))
    suf = name.rsplit(".", 1)[1]
    assert base.reading(man, f"prefill_rows_per_iter.{suf}", facts) > 1
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
    _with_fill(events, lambda i: 3)
    window = (base.T0 - 1.0, base.T1)
    facts = ring(base.facts_for(events, evicted=True, window=window))
    assert base.reading(man, name, facts) is None
    whole = ring(base.facts_for(events, evicted=False, window=window))
    assert base.reading(man, name, whole) == 3


@pytest.mark.parametrize("cell,suf", [("tiny-gpt.tiny-chat", "chat"),
                                      ("tiny-gpt.tiny-doc", "doc")])
def test_a_served_tiny_cell_reads_its_spare_rows(
        tmp_path, capsys, monkeypatch, cell, suf):
    monkeypatch.setattr(observe, "enable_compile_cache",
                        lambda: "off (tests)")
    root = tiny.make_root(str(tmp_path / "checkout"))
    run.main(["--workload", cell, "--seed", "2147484003", "--seconds", "7",
              "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    fill = res["metrics"][f"prefill_fill_rows_per_iter.{suf}"]["value"]
    rows = res["metrics"][f"prefill_rows_per_iter.{suf}"]["value"]
    programs = res["metrics"][f"prefill_programs_per_iter.{suf}"]["value"]
    # four slots, four rows a program: one program an iteration, whose
    # rows are a first chunk for every prefilling slot and the spare
    # rows' further chunks; prompts of up to four (chat) and two to five
    # (doc) chunks leave further chunks to give
    assert programs == 1 and 0 < fill < rows <= 4
    assert rows - fill >= 1
