"""The traffic generator: the seed orders the work and never changes
its amount."""

import os
from collections import Counter

import numpy as np
import pytest

import perfbench_tiny as tiny

from perfbench import manifest, traffic

CHAT = manifest.load_json(os.path.join(
    tiny.REPO, "perfbench", "traffic", "chat-open.json"))
DOC = manifest.load_json(os.path.join(
    tiny.REPO, "perfbench", "traffic", "doc-closed.json"))
VOCAB = 50304
SEEDS = [1, 7, 2147483659, 3000000001]


def counted(plans):
    return [p for p in plans if p.counted]


def test_same_seed_same_schedule():
    a = traffic.open_schedule(CHAT, 3000000001, 51, VOCAB)
    b = traffic.open_schedule(CHAT, 3000000001, 51, VOCAB)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.out_len == y.out_len
        assert x.parent == y.parent and np.array_equal(x.prompt, y.prompt)


def test_another_seed_another_order():
    a = traffic.open_schedule(CHAT, 1, 51, VOCAB)
    b = traffic.open_schedule(CHAT, 2, 51, VOCAB)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_sends_the_same_multiset_of_sizes(seed):
    ref = counted(traffic.open_schedule(CHAT, 0, 51, VOCAB))
    got = counted(traffic.open_schedule(CHAT, seed, 51, VOCAB))
    assert len(got) == len(ref) == 688  # 13.6 req/s x 51 s in blocks of 8
    for key in (lambda p: len(p.prompt), lambda p: p.out_len):
        assert Counter(map(key, got)) == Counter(map(key, ref))
    new = lambda ps, all_: sorted(  # noqa: E731
        len(p.prompt) - (len(all_[p.parent].prompt) if p.parent >= 0 else 0)
        for p in ps)
    s0 = traffic.open_schedule(CHAT, 0, 51, VOCAB)
    s1 = traffic.open_schedule(CHAT, seed, 51, VOCAB)
    assert new(counted(s0), s0) == new(counted(s1), s1)  # same prefill work


@pytest.mark.parametrize("seed", SEEDS)
def test_arrivals_are_stratified_one_to_a_slot(seed):
    plans = traffic.open_schedule(CHAT, seed, 51, VOCAB)
    rate = CHAT["rate_rps"]
    dues = [p.due_s for p in plans]
    assert dues == sorted(dues)
    first = round(min(dues) * rate - 0.5)
    assert [int(np.floor(d * rate + 1e-9)) for d in dues] \
        == list(range(first, first + len(plans)))
    win = [p.due_s for p in plans if p.counted]
    assert 0 <= min(win) and max(win) < 51


@pytest.mark.parametrize("seed", SEEDS)
def test_half_the_requests_extend_a_prompt_sent_a_fixed_gap_earlier(seed):
    plans = traffic.open_schedule(CHAT, seed, 51, VOCAB)
    gap = CHAT["share_gap_slots"]
    kids = [(i, p) for i, p in enumerate(plans) if p.parent >= 0]
    assert len(kids) == len(plans) // 2
    for i, p in kids:
        par = plans[p.parent]
        assert i - p.parent == gap and par.parent < 0
        assert len(par.prompt) < len(p.prompt)
        assert np.array_equal(p.prompt[:len(par.prompt)], par.prompt)
        assert 1.5 < len(p.prompt) / len(par.prompt) < 5


@pytest.mark.parametrize("seed", SEEDS)
def test_the_longest_prompts_are_spread_one_to_a_block(seed):
    win = counted(traffic.open_schedule(CHAT, seed, 51, VOCAB))
    gap = CHAT["share_gap_slots"]
    blocks = [win[i:i + 2 * gap] for i in range(0, len(win), 2 * gap)]
    cut = sorted(len(p.prompt) for p in win)[-len(blocks)]
    assert all(sum(len(p.prompt) >= cut for p in b) == 1 for b in blocks)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_seed_moves_whole_blocks_and_draws_the_tokens(seed):
    """Sizes, sharing and arrivals inside a block of eight slots are
    the same for every seed; the seed orders the blocks."""
    rate, size = CHAT["rate_rps"], 2 * CHAT["share_gap_slots"]

    def blocks(seed):
        win = counted(traffic.open_schedule(CHAT, seed, 51, VOCAB))
        return [tuple((len(p.prompt), p.out_len, p.parent >= 0,
                       round(p.due_s * rate % 1, 9)) for p in win[i:i + size])
                for i in range(0, len(win), size)]

    ref, got = blocks(0), blocks(seed)
    assert sorted(got) == sorted(ref) and got != ref
    assert len(set(ref)) == len(ref) == 86  # 688 requests in blocks of 8


def test_lengths_and_capacity():
    win = counted(traffic.open_schedule(CHAT, 5, 51, VOCAB))
    lens = sorted(len(p.prompt) for p in win)
    assert 16 <= lens[0] and lens[-1] <= 1536
    assert 140 <= np.median(lens) <= 180
    assert max(len(p.prompt) + p.out_len for p in win) <= 2048
    assert all(1 <= t < VOCAB for p in win for t in p.prompt[:4])


def test_quantile_lengths_are_a_fixed_ascending_multiset():
    a = traffic.quantile_lengths([[0, 16], [0.5, 160], [1, 1536]], 112)
    assert a == sorted(a) and len(a) == 112
    assert a == traffic.quantile_lengths([[0, 16], [0.5, 160], [1, 1536]], 112)
    assert 16 <= a[0] < a[-1] <= 1536


def test_a_window_that_is_no_whole_number_of_blocks_rounds_down():
    plans = traffic.open_schedule(CHAT, 3, 10, VOCAB)
    assert len(counted(plans)) == 136  # 136 slots: 17 blocks of 8
    plans = traffic.open_schedule(CHAT, 3, 2.5, VOCAB)
    assert len(counted(plans)) == 32   # 34 slots -> four blocks of 8


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_gives_every_client_the_same_work(seed):
    lists = traffic.closed_schedule(DOC, seed, 51, VOCAB)
    ref = traffic.closed_schedule(DOC, 0, 51, VOCAB)
    assert len(lists) == DOC["clients"]
    flat = [p for lst in lists for p in lst]
    flat0 = [p for lst in ref for p in lst]
    assert Counter(len(p.prompt) for p in flat) \
        == Counter(len(p.prompt) for p in flat0)
    totals = [sum(len(p.prompt) for p in lst) for lst in lists]
    assert max(totals) - min(totals) < 0.05 * max(totals)
    assert all(1024 <= len(p.prompt) <= 1792 and 32 <= p.out_len <= 128
               for p in flat)
    heads = {tuple(p.prompt[:16]) for p in flat}
    assert len(heads) == len(flat)  # no shared prefix
    # the seed deals the same lists to other clients
    shape = lambda ls: sorted(tuple((len(p.prompt), p.out_len) for p in lst)  # noqa: E731
                              for lst in ls)
    assert shape(lists) == shape(ref)
    # at every turn the clients in flight cover every stratum twice
    per = DOC["requests_per_client"]
    cuts = sorted(len(p.prompt) for p in flat)[::DOC["clients"]]
    for turn in range(per):
        strata = Counter(sum(len(lst[turn].prompt) >= c for c in cuts)
                         for lst in lists)
        assert set(strata.values()) == {DOC["clients"] // per}


def test_train_batches_are_a_function_of_seed_and_step():
    spec = {"batch": 4, "seq": 64}
    a = traffic.train_batch(spec, 2147483659, 3, 32000)
    assert a.shape == (4, 64) and a.dtype == np.int32
    assert np.array_equal(a, traffic.train_batch(spec, 2147483659, 3, 32000))
    assert not np.array_equal(a, traffic.train_batch(spec, 2147483659, 4, 32000))
    assert len({tuple(r) for r in a}) == 4  # rows that all differ
    assert 0 <= a.min() and a.max() < 32000


def test_lateness_is_taken_against_the_due_time():
    man = manifest.Manifest(tiny.REPO)
    late = man.reader("generator_late")
    ttft = man.reader("latency_percentile")
    recs = [{"counted": True, "due": 10.0, "sent": 10.004,
             "times": [10.5, 10.6]},
            {"counted": True, "due": 11.0, "sent": 11.0, "times": [11.2]},
            {"counted": False, "due": 1.0, "sent": 9.0, "times": [9.5]}]
    assert late({"requests": recs}, q=100) == pytest.approx(4.0)
    # a request the generator sent late is still timed from when it was due
    assert ttft({"requests": recs}, what="ttft", q=100) == pytest.approx(500.0)
    assert ttft({"requests": recs}, what="itl", q=50) == pytest.approx(100.0)
    assert late({}, q=99) is None
