"""``decode_live_blocks_per_step.*``: the mean of ``kv_blocks`` over the
window's ``engine.dispatch`` spans, nothing from a program that does
not write the arg, and the served tiny cell reading it end to end. The
synthetic rings are ``test_perfbench_spans``'s."""

import json

import pytest

import perfbench_tiny as tiny
import test_perfbench_spans as base

from perfbench import run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

LIVE_BLOCKS = ["decode_live_blocks_per_step.chat",
               "decode_live_blocks_per_step.doc"]


@pytest.mark.parametrize("name", LIVE_BLOCKS)
def test_live_blocks_are_a_mean_over_the_windows_steps(man, ring, name):
    events = base.steady()
    n = 0
    for e in events:
        if e["name"] == "engine.dispatch":
            e["args"]["kv_blocks"] = 100 + 2 * e["args"]["iter"]
            n += 1
    # a step before the window's start does not count
    events.append(base.ev("engine.dispatch", -50_000.0, 2.0, iter=-1,
                          kv_blocks=10_000))
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(100 + (n - 1))


@pytest.mark.parametrize("name", LIVE_BLOCKS)
def test_a_dispatch_span_without_the_count_gives_nothing(man, ring, name):
    """The parent of the PR that added ``kv_blocks``: every phase is
    there, the arg is not."""
    facts = ring(base.facts_for(base.steady()))
    assert base.reading(man, name, facts) is None


@pytest.mark.parametrize("name", LIVE_BLOCKS)
def test_a_program_that_records_no_phases_gives_nothing(man, ring, name):
    events = base.requests() + [
        base.ev("serving.step", 10.0 * k, 8.0, active=2, step=k)
        for k in range(400)]
    assert base.reading(man, name, ring(base.facts_for(events))) is None


@pytest.mark.parametrize("name", LIVE_BLOCKS)
def test_an_evicted_ring_gives_nothing(man, ring, name):
    events = [e for e in base.steady(n=200, period=20.0)
              if e["ts_ns"] > base.T0 * 1e9 + 30 * base.MS]
    for e in events:
        if e["name"] == "engine.dispatch":
            e["args"]["kv_blocks"] = 7
    window = (base.T0 - 1.0, base.T1)
    facts = ring(base.facts_for(events, evicted=True, window=window))
    assert base.reading(man, name, facts) is None
    whole = ring(base.facts_for(events, evicted=False, window=window))
    assert base.reading(man, name, whole) == 7


def test_a_served_tiny_cell_reads_its_live_blocks(tmp_path, capsys,
                                                  monkeypatch):
    monkeypatch.setattr(observe, "enable_compile_cache",
                        lambda: "off (tests)")
    root = tiny.make_root(str(tmp_path / "checkout"))
    run.main(["--workload", "tiny-gpt.tiny-chat", "--seed", "2147483931",
              "--seconds", "7", "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    # four slots of 256 positions in 16-position blocks: 64 at the most
    assert 0 < res["metrics"]["decode_live_blocks_per_step.chat"]["value"] \
        <= 64
