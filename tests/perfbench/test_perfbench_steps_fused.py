"""``steps_fused_per_step.doc`` and ``.chat``: the mean of ``fused`` (1
where the decode step's program carried the iteration's last prefill
rows too, so that the iteration read the weights once; 0 where the
step went out alone) over the window's ``engine.dispatch`` spans that
carry it: the share of decode steps that were fused. Nothing from a
program that does not write the arg (the parent of the PR that added it
writes ``kv_blocks`` and ``ahead`` alone), zero and not nothing from an
engine that never fuses, and the served tiny cells reading it end to
end. Data only: the reader is ``span_arg_mean``, the synthetic rings
are ``test_perfbench_spans``'s."""

import json

import pytest

import perfbench_tiny as tiny
import test_perfbench_spans as base
import test_perfbench_steps_ahead as ahead_base

from perfbench import manifest, run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

SUFFIXES = {"doc": ("gpt3-1p3b.doc-closed", "serve_tok_s"),
            "chat": ("gpt3-1p3b.chat-open", "itl_p99_ms")}
NAMES = [f"steps_fused_per_step.{suf}" for suf in SUFFIXES]
LAYER = "engine executables (serving.step, serving.prefill_chunk)"


def _with_fused(events, fused_of):
    """Give every ``engine.dispatch`` span ``kv_blocks`` and ``ahead``
    and, where ``fused_of(iter)`` is not None, ``fused`` and the rows
    such a step carried."""
    n = 0
    for e in events:
        if e["name"] != "engine.dispatch":
            continue
        e["args"].update(kv_blocks=100, ahead=1)
        fused = fused_of(e["args"]["iter"])
        if fused is not None:
            e["args"].update(fused=fused, prefill_rows=8 * fused)
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
                          "key": "fused"}
    names = [m["name"] for m in real.data["per_layer"]]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == LAYER
    assert entry["layer"] in {m["layer"] for m in real.data["per_layer"]
                              if m["name"] not in NAMES}
    assert (entry["unit"], entry["better"], entry["source"]) \
        == ("steps", "higher", "program_counter")
    assert names.index(name) > max(names.index(n)
                                   for n in ahead_base.NAMES)
    assert [n for n in names if n in NAMES] == NAMES
    assert real.cell(cell)
    # the cell reports the end-to-end metric this one moves
    (e2e,) = [m for m in real.data["end_to_end"] if m["name"] == moves]
    assert cell in e2e["workloads"]


def test_the_ouro_entries_stay_together_where_pr_37_put_them():
    """What ``test_perfbench_ouro.py`` says of ``per_layer``'s last
    sixteen entries (``tests/conftest.py`` marks that case, which these
    two entries push out of place), held by the entries' order: the
    sixteen that list the Ouro cell are consecutive, every entry behind
    them is one of ``NAMES``, each moves ``serve_tok_s`` and has a
    metric file whose reader exists."""
    import os

    import perfbench_tiny_ouro as tiny_ouro

    real = manifest.Manifest(tiny.REPO)
    per_layer = real.data["per_layer"]
    at = [i for i, m in enumerate(per_layer)
          if m.get("workloads") == [tiny_ouro.CELL]]
    assert len(at) == 16 and at == list(range(at[0], at[0] + 16))
    assert [m["name"] for m in per_layer[at[-1] + 1:]] == NAMES
    assert {"loop_passes_per_step.ouro", "loop_attn_roofline.ouro",
            "loop_step_roofline.ouro"} \
        <= {per_layer[i]["name"] for i in at}
    for i in at:
        m = per_layer[i]
        assert m["moves"] == "serve_tok_s"
        mf = real.metric_file(m["name"])
        assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
        assert os.path.isfile(os.path.join(
            tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))
    serve_cells = next(m["workloads"] for m in real.data["end_to_end"]
                       if m["name"] == "serve_tok_s")
    assert serve_cells[-1] == tiny_ouro.CELL and len(serve_cells) == 3


@pytest.mark.parametrize("name", NAMES)
def test_the_mean_is_the_share_of_steps_that_carried_prefill_rows(
        man, ring, name):
    events = base.steady()
    # one iteration in eight had a prompt to feed: its step carried the
    # rows, the others went out alone
    n = _with_fused(events, lambda i: int(i % 8 == 0))
    assert n == 40
    # a step before the window's start does not count
    events.append(base.ev("engine.dispatch", -50_000.0, 2.0, iter=-1,
                          kv_blocks=100, ahead=1, fused=1, prefill_rows=8))
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(5 / 40)


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_dispatch_span_gives_nothing(man, ring, name):
    """The parent writes ``kv_blocks`` and ``ahead`` and no ``fused``:
    the older metrics read, this one has nothing to read and the line
    leaves it out."""
    events = base.steady()
    assert _with_fused(events, lambda i: None) == 0
    facts = ring(base.facts_for(events))
    suf = name.rsplit(".", 1)[1]
    assert base.reading(man, f"steps_ahead_per_step.{suf}", facts) == 1
    assert base.reading(man, name, facts) is None


@pytest.mark.parametrize("name", NAMES)
def test_an_engine_that_never_fuses_reads_zero_not_nothing(man, ring, name):
    """A windowed, looped or speculative engine writes ``fused`` 0 a
    step."""
    events = base.steady()
    _with_fused(events, lambda i: 0)
    assert base.reading(man, name, ring(base.facts_for(events))) == 0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_that_records_no_phases_gives_nothing(man, ring, name):
    events = base.requests() + [
        base.ev("serving.step", 10.0 * k, 8.0, active=2, step=k)
        for k in range(400)]
    assert base.reading(man, name, ring(base.facts_for(events))) is None


@pytest.mark.parametrize("cell,suf,least,most", [
    ("tiny-gpt.tiny-doc", "doc", 0.1, 1.0),
    ("tiny-gpt.tiny-chat", "chat", 0.0, 0.9)])
def test_a_served_tiny_cell_reads_its_fused_steps(
        tmp_path, capsys, monkeypatch, cell, suf, least, most):
    monkeypatch.setattr(observe, "enable_compile_cache",
                        lambda: "off (tests)")
    root = tiny.make_root(str(tmp_path / "checkout"))
    run.main(["--workload", cell, "--seed", "2147484073", "--seconds", "7",
              "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    fused = res["metrics"][f"steps_fused_per_step.{suf}"]["value"]
    # the closed loop mostly has a prompt to feed beside its decode
    # rows; the open loop's few steps mostly go out alone (the reading
    # is there all the same: zero, not nothing)
    assert least <= fused <= most
    assert res["metrics"][f"compiles_in_window.{suf}"]["value"] == 0
