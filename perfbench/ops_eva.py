"""Operations and bytes that EVA attention (chunked linearized
attention: exact keys inside a window, one summary per chunk behind it)
needs in a decode step, from the client's side and from shapes alone.
As ``ops_bytes.py``: the least a correct implementation must do, so a
roofline share cannot pass 100%.

The summarisation (K and V of each filled chunk read once) is left to
XLA in the program, shows as no Pallas call in a trace and has no
metric; its count is not kept here either.
"""

from __future__ import annotations


def attended(position, window, chunk):
    """(exact keys, summaries) that the query at absolute ``position``
    attends: the keys of its window up to itself, and one summary for
    every chunk of every window behind it."""
    return position % window + 1, position // window * (window // chunk)


def decode_entries(prompt_len, j, window, chunk):
    """Entries attended by the decode step that produced a request's
    ``j``-th output token (``j`` >= 1; token 0 comes from the prefill):
    its query is token ``j - 1``, at position ``prompt_len + j - 1``."""
    exact, summaries = attended(prompt_len + j - 1, window, chunk)
    return exact + summaries


def attention_cost(cfg, entries, dtype_bytes=2):
    """(flops, bytes) of decode attention over all layers for a sum of
    ``entries`` cached entries attended (one query a row and step): K
    and V of every entry, exact key or summary, are read once."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return layers * entries * 4 * h, layers * entries * 2 * h * dtype_bytes
