"""``steps_ahead_per_step.chat``, ``.doc`` and ``.eva``: the mean of
``ahead`` (1 where a decode step was enqueued while the step before it
was still unread, 0 where the device had drained) over the window's
``engine.dispatch`` spans that carry it: the share of steps the host
was a step ahead of. Nothing from a program that does not write the arg
(the parent of the PR that added it writes ``kv_blocks`` alone), and
the served tiny cells reading it end to end. Data only: the reader is
``span_arg_mean``, the synthetic rings are ``test_perfbench_spans``'s."""

import json

import pytest

import perfbench_tiny as tiny
import test_perfbench_fill_rows as fill_base
import test_perfbench_spans as base

from perfbench import manifest, run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

SUFFIXES = {"chat": ("gpt3-1p3b.chat-open", "itl_p99_ms"),
            "doc": ("gpt3-1p3b.doc-closed", "serve_tok_s"),
            "eva": ("evabyte-6p5b-cut.doc-bytes-closed", "serve_tok_s")}
NAMES = [f"steps_ahead_per_step.{suf}" for suf in SUFFIXES]
LAYER = "serving entry (serving/engine.py scheduler, block pool)"


def _with_ahead(events, ahead_of):
    """Give every ``engine.dispatch`` span ``kv_blocks`` and, where
    ``ahead_of(iter)`` is not None, ``ahead``."""
    n = 0
    for e in events:
        if e["name"] != "engine.dispatch":
            continue
        e["args"]["kv_blocks"] = 100
        ahead = ahead_of(e["args"]["iter"])
        if ahead is not None:
            e["args"]["ahead"] = ahead
            n += 1
    return n


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_data_beside_the_accepted_ones(name):
    """A file of arguments for the reader the benchmark has, and an
    entry of ``per_layer`` that lists its one cell, put after every
    entry that was there, in the order of ``NAMES``."""
    real = manifest.Manifest(tiny.REPO)
    cell, moves = SUFFIXES[name.rsplit(".", 1)[1]]
    mf = real.metric_file(name)
    assert mf["reader"] == "span_arg_mean"
    assert mf["args"] == {"trace": "engine", "span": "engine.dispatch",
                          "key": "ahead"}
    names = [m["name"] for m in real.data["per_layer"]]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == LAYER
    assert entry["layer"] in {m["layer"] for m in real.data["per_layer"]
                              if m["name"] not in NAMES}
    assert (entry["unit"], entry["better"], entry["source"]) \
        == ("steps", "higher", "program_counter")
    assert names.index(name) > max(names.index(n) for n in fill_base.NAMES)
    assert [n for n in names if n in NAMES] == NAMES
    assert real.cell(cell)
    # the cell reports the end-to-end metric this one moves
    (e2e,) = [m for m in real.data["end_to_end"] if m["name"] == moves]
    assert cell in e2e["workloads"]


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_the_share_of_steps_enqueued_ahead(man, ring, name):
    events = base.steady()
    # one step in eight found the device drained (a flush, an engine
    # that had idled): it counts as a step that was not ahead
    n = _with_ahead(events, lambda i: int(i % 8 != 0))
    assert n == 40
    # a step before the window's start does not count
    events.append(base.ev("engine.dispatch", -50_000.0, 2.0, iter=-1,
                          kv_blocks=100, ahead=0))
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(35 / 40)


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_dispatch_span_gives_nothing(man, ring, name):
    """The parent writes ``kv_blocks`` and no ``ahead``: the older
    metric reads, this one has nothing to read and the line leaves it
    out."""
    events = base.steady()
    assert _with_ahead(events, lambda i: None) == 0
    facts = ring(base.facts_for(events))
    suf = name.rsplit(".", 1)[1]
    assert base.reading(man, f"decode_live_blocks_per_step.{suf}",
                        facts) == 100
    assert base.reading(man, name, facts) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_speculative_engine_reads_zero_not_nothing(man, ring, name):
    """A lane that stays synchronous writes ``ahead`` 0 a step."""
    events = base.steady()
    _with_ahead(events, lambda i: 0)
    assert base.reading(man, name, ring(base.facts_for(events))) == 0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_that_records_no_phases_gives_nothing(man, ring, name):
    events = base.requests() + [
        base.ev("serving.step", 10.0 * k, 8.0, active=2, step=k)
        for k in range(400)]
    assert base.reading(man, name, ring(base.facts_for(events))) is None


@pytest.mark.parametrize("name", NAMES)
def test_an_evicted_ring_gives_nothing(man, ring, name):
    events = [e for e in base.steady(n=200, period=20.0)
              if e["ts_ns"] > base.T0 * 1e9 + 30 * base.MS]
    _with_ahead(events, lambda i: 1)
    window = (base.T0 - 1.0, base.T1)
    facts = ring(base.facts_for(events, evicted=True, window=window))
    assert base.reading(man, name, facts) is None
    whole = ring(base.facts_for(events, evicted=False, window=window))
    assert base.reading(man, name, whole) == 1


@pytest.mark.parametrize("cell,suf", [
    ("tiny-gpt.tiny-chat", "chat"), ("tiny-gpt.tiny-doc", "doc"),
    ("tiny-evabyte.tiny-doc-bytes", "eva")])
def test_a_served_tiny_cell_reads_its_steps_ahead(
        tmp_path, capsys, monkeypatch, cell, suf):
    monkeypatch.setattr(observe, "enable_compile_cache",
                        lambda: "off (tests)")
    root = tiny.make_root(str(tmp_path / "checkout"))
    run.main(["--workload", cell, "--seed", "2147484071", "--seconds", "7",
              "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    ahead = res["metrics"][f"steps_ahead_per_step.{suf}"]["value"]
    # a closed loop never idles: only the first step after the engine
    # starts finds the device drained; the open loop idles between
    # arrivals, and every step after an idle stretch starts afresh
    assert (0.9 if suf != "chat" else 0.5) < ahead <= 1.0
