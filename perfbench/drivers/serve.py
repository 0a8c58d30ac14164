"""Drives a served model: open loop or closed loop, from the client's
side. The program gets prompts and an ``on_token`` callback; every time
is taken here, on this process's clock, when a token is handed over.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from .. import traffic as traffic_mod

DRAIN_LIMIT_S = 120.0
FIRST_TOKEN_LIMIT_S = 60.0


class Live:
    """One request as the client saw it."""

    def __init__(self, plan, index):
        self.plan, self.index = plan, index
        self.due = self.sent = None
        self.times = []      # arrival time of each output token
        self.handle = None
        self.on_last = None

    def on_token(self, _req, _token):
        self.times.append(time.perf_counter())
        if self.on_last is not None and len(self.times) == self.plan.out_len:
            self.on_last()


def _send(prog, engine, live, due):
    live.due = due
    live.sent = time.perf_counter()
    live.handle = prog.submit(engine, live.plan.prompt, live.plan.out_len,
                              live.on_token)


def run_open(prog, engine, spec, seed, seconds, vocab, tracer):
    """Send the schedule on its clock, count the requests due inside
    the window, drain them. Returns (every Live sent, window)."""
    plans = traffic_mod.open_schedule(spec, seed, seconds, vocab)
    lives = [Live(p, i) for i, p in enumerate(plans)]
    counted = [lv for lv in lives if lv.plan.counted]
    t_zero = time.perf_counter() + 0.02 - plans[0].due_s
    t_end = t_zero + seconds
    started = False
    n_sent = 0
    for lv in lives:
        due = t_zero + lv.plan.due_s
        while True:
            now = time.perf_counter()
            if not started and now >= t_zero:
                started = True
                tracer.window_start(t_end)
            if now >= due:
                break
            time.sleep(min(due - now, 0.02))
        # the tail keeps the load up only while counted requests drain
        if now >= t_end and all(c.handle is not None and c.handle.done
                                for c in counted):
            break
        _send(prog, engine, lv, due)
        n_sent += 1
    time.sleep(max(0.0, t_end - time.perf_counter()))
    t_drain = time.perf_counter()
    for c in counted:
        left = DRAIN_LIMIT_S - (time.perf_counter() - t_drain)
        if c.handle is not None and not c.handle.done and left > 0:
            try:
                c.handle.result(timeout=left)
            except TimeoutError:
                pass
    drained = time.perf_counter() - t_end
    print(f"[perfbench] open loop: {len(counted)} counted of {n_sent} sent "
          f"({len(plans)} scheduled), drained {drained:.2f}s after the "
          f"window", flush=True)
    return [lv for lv in lives if lv.handle is not None], (t_zero, t_end)


def run_closed(prog, engine, spec, seed, seconds, vocab, tracer):
    """``clients`` callers, each sending its next request when its last
    answer ends. Returns (every Live sent, window)."""
    lists = traffic_mod.closed_schedule(spec, seed, seconds, vocab)
    done_q = queue.Queue()
    sent, nxt = [], [0] * len(lists)

    def send_next(c):
        plan = lists[c][nxt[c] % len(lists[c])]
        nxt[c] += 1
        lv = Live(plan, len(sent))
        lv.on_last = lambda c=c: done_q.put(c)
        sent.append(lv)
        _send(prog, engine, lv, time.perf_counter())

    t_start = time.perf_counter()
    for c in range(len(lists)):
        send_next(c)
    t_zero = t_start + float(spec["lead_in_s"])
    t_end = t_zero + seconds
    started = False
    while True:
        now = time.perf_counter()
        if not started and now >= t_zero:
            started = True
            tracer.window_start(t_end)
        if now >= t_end:
            break
        try:
            c = done_q.get(timeout=min(0.02, t_end - now))
        except queue.Empty:
            continue
        send_next(c)
    print(f"[perfbench] closed loop: {len(sent)} requests sent by "
          f"{len(lists)} clients", flush=True)
    return sent, (t_zero, t_end)


def await_first_tokens(lives):
    """After a closed loop's window has shut, and outside it: wait for
    the first token of every request that was still prefilling. The
    window credits a prompt evenly over the time from its submission to
    its first token (``stats.tokens_in_window``), so a prompt with no
    first token yet was credited nothing for the chunks it had run
    inside, a step of 1.6% of a window for each such request that came
    and went with the smallest change of pace (PERF.md, PR 28). Nothing
    is sent meanwhile; a request that fails or never answers is left as
    it is. Returns (requests waited for, seconds waited)."""
    t0 = time.perf_counter()
    pending = [lv for lv in lives if not lv.times and not lv.handle.done]
    n = len(pending)
    while pending and time.perf_counter() - t0 < FIRST_TOKEN_LIMIT_S:
        time.sleep(0.005)
        pending = [lv for lv in pending
                   if not lv.times and not lv.handle.done]
    return n, time.perf_counter() - t0


def records(prog, lives, kind):
    """Plain records of what each request did, for the readers and the
    check. A request that failed, was refused or came back with another
    length than asked is ``ok: False``; in a closed loop one still
    running when the window shut is neither (``final: False``)."""
    out = []
    for lv in lives:
        st = prog.request_state(lv.handle) if lv.handle is not None else {
            "completed": False, "final": True, "tokens": [],
            "queue_wait_s": None}
        whole = st["completed"] and len(st["tokens"]) == lv.plan.out_len \
            and len(lv.times) == lv.plan.out_len
        final = st["final"] or (kind == "open" and lv.plan.counted)
        out.append({
            "index": lv.index, "due": lv.due, "sent": lv.sent,
            "times": list(lv.times), "prompt_len": len(lv.plan.prompt),
            "out_len": lv.plan.out_len, "prompt": lv.plan.prompt,
            "tokens": st["tokens"], "queue_wait_s": st["queue_wait_s"],
            "final": final, "ok": whole, "counted": lv.plan.counted,
            "extends": lv.plan.parent >= 0})
    return out


def sample_for_check(recs, k, seed):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    ok = [r for r in recs if r["ok"]]
    if not ok:
        return []
    longest = max(ok, key=lambda r: r["prompt_len"] + r["out_len"])
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(ref, params, cfg, sample, chooser=None, rows=256, pad=256):
    """For every served token of ``sample``, how far its logit lies
    below the reference's best at that position. With ``chooser`` (a
    lower-precision matmul) the token judged is the one the reference
    computed with that matmul puts first, which is how a control is
    read without decoding. Returns the list of gaps."""
    import jax.numpy as jnp

    gaps = []
    for r in sample:
        ids = np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)])
        n, first = r["out_len"], r["prompt_len"] - 1
        padded = -(-len(ids) // pad) * pad
        rows = min(rows, padded)
        buf = np.zeros(padded, np.int32)
        buf[:len(ids)] = ids
        start = max(0, min(first, padded - rows))
        off = first - start
        logits = np.asarray(ref.logit_rows(
            params, jnp.asarray(buf), start, rows, cfg))[off:off + n]
        if chooser is None:
            chosen = np.asarray(r["tokens"])
        else:
            chosen = np.asarray(ref.logit_rows(
                params, jnp.asarray(buf), start, rows, cfg, mm=chooser)
            )[off:off + n].argmax(-1)
        gaps.extend((logits.max(-1)
                     - logits[np.arange(n), chosen]).tolist())
    return gaps
