"""One general traffic generator, driven by a data file.

A traffic file names its ``kind`` and gives parameters; nothing here
knows a cell's name. Whatever the seed, a schedule holds the same
multiset of sizes and the same sharing between prompts, in another
order, and every token is the seed's. So two seeds differ in order and
never in the amount of work, which is what lets a bound be tight.

kinds
  open    requests arrive on a clock at ``rate_rps`` whatever the
          system does (independent users). Three segments of the same
          make, a lead-in, the counted window and a tail that keeps the
          load up while the counted requests drain.
  closed  ``clients`` callers that each send their next request when
          the last answer ends.
  train   batches of token ids, ``batch`` rows of ``seq``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def quantile_lengths(points, n):
    """A fixed multiset of ``n`` whole lengths: the piecewise
    log-linear quantile function through ``points`` ([[q, length], ...]
    with q rising from 0 to 1) read at (i + 0.5) / n. Ascending."""
    qs = [p[0] for p in points]
    logs = [math.log(p[1]) for p in points]
    at = (np.arange(n) + 0.5) / n
    return [int(round(math.exp(v))) for v in np.interp(at, qs, logs)]


@dataclass
class Planned:
    """One request as the schedule fixes it before anything is sent."""
    due_s: float            # relative to the start of the counted window
    prompt: np.ndarray      # int32 token ids
    out_len: int
    counted: bool
    parent: int = -1        # index of the request whose prompt this one extends
    client: int = -1        # closed loop: which caller sends it


def _tokens(rng, n, vocab):
    return rng.integers(1, vocab, size=n, dtype=np.int64).astype(np.int32)


def _segment(spec, rng, n_slots, vocab):
    """(prompt, out_len, parent offset or 0, jitter) for ``n_slots``
    consecutive slots of an open schedule.

    The ascending multiset of prompt lengths is cut in quarters: the
    first and third are first turns, the second and fourth extend them,
    rank for rank, so a follow-up is some two to three times as long as
    the turn it continues, as a chat history grows. Pairs are laid out
    in blocks of ``gap`` openers and then their ``gap`` follow-ups, so a
    follow-up always arrives ``gap`` slots after the prompt it extends,
    and every block holds exactly one follow-up from each of ``gap``
    strata of length: the longest prompts, which make the tail of the
    time to a first token, are spread evenly over the run instead of
    colliding by chance.

    Which pairs share a block, their order inside it, every slot's
    output length and its arrival inside the slot are the same for
    every seed (drawn from a generator of their own). The seed puts the
    blocks in another order and draws the tokens. A time to a first
    token depends on what arrives in the few seconds around it, so with
    whole blocks moved two seeds do the same work in another order;
    with every slot shuffled they did not, and the p90 of one seed lay
    up to 8% from another's where two runs of one seed agreed to 1%
    (PERF.md, PR 24).
    """
    gap = int(spec["share_gap_slots"])
    if n_slots % (2 * gap):
        raise ValueError(f"{n_slots} slots is no multiple of {2 * gap}")
    quarter, blocks = n_slots // 4, n_slots // (2 * gap)
    prompts = quantile_lengths(spec["prompt_quantiles"], n_slots)
    outs = quantile_lengths(spec["output_quantiles"], n_slots)
    fixed = np.random.default_rng([0xB10C, n_slots])
    out_of_slot = [outs[i] for i in fixed.permutation(n_slots)]
    jitter = fixed.random(n_slots)
    # stratum s holds the pairs of rank s*blocks .. (s+1)*blocks-1
    strata = [s * blocks + fixed.permutation(blocks) for s in range(gap)]
    order_in_block = [fixed.permutation(gap) for _ in range(blocks)]
    plan = []
    for b in rng.permutation(blocks):
        pairs = [int(strata[s][b]) for s in order_in_block[b]]
        # pair p: opener of rank p in quarters 0 and 2 together,
        # follow-up of the same rank in quarters 1 and 3
        first = [p if p < quarter else p + quarter for p in pairs]
        slot0 = 2 * gap * int(b)  # the block's own sizes and arrivals
        openers = [_tokens(rng, prompts[i], vocab) for i in first]
        turns = [(tok, 0) for tok in openers]
        for i, tok in zip(first, openers):
            extra = prompts[i + quarter] - len(tok)
            if extra > 0:
                turns.append((np.concatenate(
                    [tok, _tokens(rng, extra, vocab)]), -gap))
            else:  # equal lengths at the middle of the multiset
                turns.append((_tokens(rng, prompts[i + quarter], vocab), 0))
        plan.extend((tok, out_of_slot[slot0 + j], off, jitter[slot0 + j])
                    for j, (tok, off) in enumerate(turns))
    return plan


def open_schedule(spec, seed, seconds, vocab):
    """Planned requests of an open loop, sorted by due time. Slot k of
    a segment covers [k, k+1) / rate; its request is due at a fixed
    point inside it."""
    rng = np.random.default_rng([int(seed), 0x0BE7])
    rate, gap2 = float(spec["rate_rps"]), 2 * int(spec["share_gap_slots"])

    def slots(s):
        return max(gap2, int(rate * s + 1e-9) // gap2 * gap2)

    n_lead, n_win, n_tail = (slots(spec["lead_in_s"]), slots(seconds),
                             slots(spec["tail_s"]))
    out, first = [], -n_lead
    for n, counted in ((n_lead, False), (n_win, True), (n_tail, False)):
        base = len(out)
        for i, (tok, out_len, off, jit) in enumerate(
                _segment(spec, rng, n, vocab)):
            out.append(Planned((first + i + float(jit)) / rate, tok, out_len,
                               counted, parent=base + i + off if off else -1))
        first += n
    return out


def closed_schedule(spec, seed, seconds, vocab):
    """Per client, the list of requests it will send in turn; a client
    that runs out starts its list again. No two prompts share a prefix.

    The ascending multisets of prompt and output lengths are cut into
    ``requests_per_client`` strata and every client gets one length
    from each, so all clients carry the same work. Client c starts at
    stratum c mod ``requests_per_client``: as the clients move on
    roughly together, every stratum is in flight at any time, and a
    window sees the same mix wherever it falls. All of that is the same
    for every seed; the seed deals the lists to the clients and draws
    the tokens."""
    rng = np.random.default_rng([int(seed), 0xC105ED])
    clients, per = int(spec["clients"]), int(spec["requests_per_client"])
    n = clients * per
    prompts = quantile_lengths(spec["prompt_quantiles"], n)
    outs = quantile_lengths(spec["output_quantiles"], n)
    fixed = np.random.default_rng([0xC105ED, n])
    sizes = [[None] * per for _ in range(clients)]
    for s in range(per):
        p_idx = s * clients + fixed.permutation(clients)
        o_idx = s * clients + fixed.permutation(clients)
        for c in range(clients):
            sizes[c][s] = (prompts[p_idx[c]], outs[o_idx[c]])
    planned = []
    for c, which in enumerate(rng.permutation(clients)):
        turns = [sizes[which][(which + j) % per] for j in range(per)]
        planned.append([Planned(0.0, _tokens(rng, p, vocab), o, True, client=c)
                        for p, o in turns])
    return planned


def train_batch(spec, seed, step, vocab):
    """Token ids ``[batch, seq]`` of one step: rows that all differ,
    a pure function of (seed, step)."""
    rng = np.random.default_rng([int(seed), 0x7A1, int(step)])
    return rng.integers(0, vocab, size=(int(spec["batch"]), int(spec["seq"])),
                        dtype=np.int64).astype(np.int32)
