"""A closed loop's window and the prompts still prefilling as it shuts:
the harness waits for their first token outside the window, so that the
chunks they ran inside are credited (``serve.await_first_tokens``,
``readers/serve_tokens_per_s.py``)."""

import threading
import time
import types

import pytest

from perfbench.drivers import serve
from perfbench.readers import serve_tokens_per_s


def _live(out_len=4):
    lv = serve.Live(types.SimpleNamespace(out_len=out_len), 0)
    lv.handle = types.SimpleNamespace(done=False)
    return lv


def test_the_wait_ends_with_the_last_first_token():
    lives = [_live(), _live(), _live()]
    lives[0].times.append(time.perf_counter())   # already decoding

    def answer():
        for lv in lives[1:]:
            time.sleep(0.05)
            lv.on_token(None, 7)

    t = threading.Thread(target=answer)
    t.start()
    n, waited = serve.await_first_tokens(lives)
    t.join()
    assert all(len(lv.times) == 1 for lv in lives)
    assert n == 2 and 0.09 < waited < 1.0


def test_nothing_still_prefilling_means_no_wait():
    lives = [_live(), _live()]
    for lv in lives:
        lv.times.append(0.0)
    n, waited = serve.await_first_tokens(lives)
    assert n == 0 and waited < 0.05


def test_a_request_that_failed_is_not_waited_for(monkeypatch):
    monkeypatch.setattr(serve, "FIRST_TOKEN_LIMIT_S", 5.0)
    lv = _live()
    lv.handle.done = True           # refused or failed: no token will come
    n, waited = serve.await_first_tokens([lv])
    assert n == 0 and waited < 0.5 and lv.times == []


def test_one_that_never_answers_is_left_at_the_limit(monkeypatch):
    monkeypatch.setattr(serve, "FIRST_TOKEN_LIMIT_S", 0.1)
    lv = _live()
    n, waited = serve.await_first_tokens([lv])
    assert n == 1 and 0.1 <= waited < 1.0 and lv.times == []


def _rec(sent, times, prompt_len):
    return {"sent": sent, "times": times, "prompt_len": prompt_len}


@pytest.mark.parametrize("first_token,credited", [
    (12.0, 1000 * 2 / 4),    # sent at 8, window shuts at 10: half inside
    (18.0, 1000 * 2 / 10),   # a longer wait past the window: a fifth
    (None, 0.0),             # never came: nothing to credit it by
])
def test_a_prompt_half_prefilled_as_the_window_shuts_counts_its_part(
        first_token, credited):
    facts = {"window": (0.0, 10.0), "requests": [
        _rec(1.0, [2.0, 3.0, 9.99, 10.0], 100),
        _rec(8.0, [] if first_token is None else [first_token], 1000)]}
    # the first request: its prompt whole, three of four tokens inside
    assert serve_tokens_per_s.read(facts) == pytest.approx(
        (100 + 3 + credited) / 10.0)


def test_the_rate_by_slices_adds_up_to_the_rate_of_the_window():
    facts = {"window": (0.0, 12.0), "requests": [
        _rec(-1.0, [1.0 + 0.5 * k for k in range(20)], 300),
        _rec(5.0, [8.0, 8.5, 13.0], 900)]}
    slices = serve_tokens_per_s.by_slice(facts)
    assert len(slices) == 4
    assert sum(slices) / 4 == pytest.approx(
        serve_tokens_per_s.read(facts), abs=1.0)
    # the second prompt is prefilled over [5, 8]: a third in the slice
    # [3, 6), two thirds in [6, 9)
    assert slices[1] == round((300 + 6) / 3.0)
    assert slices[2] == round((600 + 6 + 2) / 3.0)
