"""``generation.cached_attention``: the one cached-attention call under
the model files.

Every case drives the seam as one of the three model families does
(gpt: MHA; llama: GQA 4 over 2; evabyte: MHA at a virtual position with
the summary write between the cache write and the read) over one cache
layout, with the Pallas kernel on (interpreted here) and off, and holds
three things: the output against plain softmax attention over the dense
view of the cache the call returned; that cache against
``update_static_kv_cache`` alone; and the one dispatch counter bumped,
by its exact label (``perfbench/programs/observe.py`` sums them). A
structural test holds that no model file decides a cache format or a
kernel any more.
"""

import ast
import inspect
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import (cached_attention, eva_summary_write,
                                   eva_virtual_position, kv_cache_layout,
                                   update_static_kv_cache)
from paddle_tpu.pallas_kernels import decode_attention as fd
from paddle_tpu.quantization import intx

B, H, D = 2, 4, 32
BS, NB = 8, 10                      # paged: 10 blocks of 8 a row
MAX_LEN = BS * NB
W, C = 64, 16                       # EVA window and chunk
KV_HEADS = {"gpt": 4, "llama": 2, "evabyte": 4}
TREE = np.array([[1, 0, 0], [1, 1, 0], [1, 0, 1]], bool)  # two children


def _rand(rng, *shape):
    return paddle.to_tensor(rng.normal(size=shape).astype(np.float32))


def _empty_cache(kind, kv):
    """A zeroed cache dict of the layout ``kind`` names."""
    paged = kind.startswith("paged") or kind == "external_mask"
    quant = "int8" in kind or kind == "external_mask"
    lead = (1 + B * NB, BS) if paged else (B, MAX_LEN)
    cache = {n: jnp.zeros(lead + (kv, D), jnp.int8 if quant else jnp.float32)
             for n in ("k", "v")}
    if quant:
        cache.update({n: jnp.zeros(lead + (kv,), jnp.float32)
                      for n in ("ks", "vs")})
    if paged:
        # each row its own blocks, in an order that is not the pool's
        order = np.random.default_rng(5).permutation(B * NB) + 1
        cache["bt"] = jnp.asarray(order.reshape(B, NB), jnp.int32)
    return cache


def _dense(cache):
    """float32 ``(k, v)`` [b, max_len, kv, d] of what a cache dict holds."""
    out = []
    for n in ("k", "v"):
        a = np.asarray(cache[n]._data if hasattr(cache[n], "_data")
                       else cache[n])
        if n + "s" in cache:
            sc = cache[n + "s"]
            sc = np.asarray(sc._data if hasattr(sc, "_data") else sc)
            a = np.asarray(intx.unpack_absmax(a, sc[..., None], "int8"))
        if "bt" in cache:
            a = a[np.asarray(cache["bt"])].reshape((B, MAX_LEN) + a.shape[2:])
        out.append(a.astype(np.float32))
    return out


def _plain_attention(q, k, v, visible):
    """softmax(q k^T / sqrt(d)) v with ``visible`` [b, s, max_len]; kv
    heads repeated to the query's."""
    rep = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, rep, 2), np.repeat(v, rep, 2)
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    sc = np.where(visible[:, None], sc, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), v)


def _counts():
    return {(fam, s["labels"]["model" if fam == "hit" else "reason"]):
            s["value"]
            for fam, c in (("hit", fd._fd_hits), ("fallback", fd._fd_fallbacks))
            for s in c.collect()}


def _expected_label(family, kind, kernel):
    paged, quant = kv_cache_layout(_empty_cache(kind, 1))
    if not kernel:
        reason = "disabled"
    elif kind == "external_mask":
        reason = "external_mask"
    else:
        return ("hit", family + "_paged" * paged + "_quant" * quant)
    return ("fallback", "paged_" * paged + "quant_" * quant + reason)


KINDS = ("contiguous", "contiguous_int8", "paged", "paged_int8",
         "paged_tree", "external_mask")
CASES = [(f, k) for f in ("gpt", "llama") for k in KINDS] \
    + [("evabyte", "paged")]


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
@pytest.mark.parametrize("family,kind", CASES,
                         ids=[f"{f}-{k}" for f, k in CASES])
def test_output_cache_and_counter(family, kind, kernel, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1" if kernel else "0")
    rng = np.random.default_rng(17)
    kv = KV_HEADS[family]
    s = 3 if kind == "paged_tree" else 1
    eva = family == "evabyte"
    # a prefix of 48 positions in every row (what lies beyond a row's
    # own length is garbage the read must not see), then the step
    pos = np.array([31, 47] if eva else [5, 11], np.int32)
    cache = _empty_cache(kind, kv)
    with paddle.no_grad():
        _, _, cache, _ = update_static_kv_cache(
            cache, _rand(rng, B, 48, kv, D), _rand(rng, B, 48, kv, D), 0,
            build_mask=False, gather=False)
        if kind == "paged_tree":
            cache["tree_mask"] = jnp.broadcast_to(TREE, (B, 3, 3))
        q, k, v = _rand(rng, B, s, H, D), _rand(rng, B, s, kv, D), \
            _rand(rng, B, s, kv, D)
        key_pos = np.arange(MAX_LEN)[None, None, :]
        q_pos = pos[:, None, None] + np.arange(s)[None, :, None]
        visible = key_pos <= q_pos
        if kind == "paged_tree":
            visible = np.repeat(key_pos < pos[:, None, None], 3, 1)
            for i in range(3):
                for j in np.flatnonzero(TREE[i]):
                    visible[np.arange(B), i, pos + j] = True
        kwargs = {}
        if kind == "external_mask":
            visible = visible & (key_pos != 0)   # a left pad at position 0
            kwargs["attn_mask"] = paddle.to_tensor(np.where(
                visible[:, None], 0.0, -1e30).astype(np.float32))
        write_pos = jnp.asarray(pos)
        if eva:
            phi, mu = _rand(rng, H, D), _rand(rng, H, D)

            def summaries(c):
                return eva_summary_write(c, phi, mu, jnp.asarray(pos), s, W,
                                         C, D ** -0.5)

            kwargs["after_write"] = summaries
            write_pos = eva_virtual_position(write_pos, W, C)
        _, _, want_cache, _ = update_static_kv_cache(
            dict(cache), k, v, write_pos, build_mask=False, gather=False)
        if eva:
            want_cache = summaries(want_cache)
        before = _counts()
        out, new_cache = cached_attention(q, k, v, cache, write_pos,
                                          family=family, **kwargs)
        after = _counts()

    assert new_cache.keys() == want_cache.keys()
    for name in want_cache:
        np.testing.assert_array_equal(
            np.asarray(getattr(new_cache[name], "_data", new_cache[name])),
            np.asarray(getattr(want_cache[name], "_data", want_cache[name])),
            err_msg=name)
    want = _plain_attention(np.asarray(q._data), *_dense(new_cache), visible)
    np.testing.assert_allclose(np.asarray(out._data), want, atol=2e-5,
                               rtol=2e-5)
    bumped = {key: n - before.get(key, 0) for key, n in after.items()
              if n != before.get(key, 0)}
    assert bumped == {_expected_label(family, kind, kernel): 1}


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_a_tree_bundle_over_a_contiguous_cache_declines_like_a_mask(
        kernel, monkeypatch):
    """The contiguous kernel has no mask input: the bundle is read by
    the XLA path under the ancestor mask, whatever the switch says."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1" if kernel else "0")
    rng = np.random.default_rng(3)
    cache = _empty_cache("contiguous", 2)
    pos = np.array([5, 11], np.int32)
    with paddle.no_grad():
        _, _, cache, _ = update_static_kv_cache(
            cache, _rand(rng, B, 16, 2, D), _rand(rng, B, 16, 2, D), 0,
            build_mask=False)
        cache["tree_mask"] = jnp.broadcast_to(TREE, (B, 3, 3))
        q, k, v = _rand(rng, B, 3, H, D), _rand(rng, B, 3, 2, D), \
            _rand(rng, B, 3, 2, D)
        before = _counts()
        out, new_cache = cached_attention(q, k, v, cache, jnp.asarray(pos),
                                          family="llama")
        after = _counts()
    visible = np.arange(MAX_LEN)[None, None, :] < pos[:, None, None]
    visible = np.repeat(visible, 3, 1)
    for i in range(3):
        for j in np.flatnonzero(TREE[i]):
            visible[np.arange(B), i, pos + j] = True
    want = _plain_attention(np.asarray(q._data), *_dense(new_cache), visible)
    np.testing.assert_allclose(np.asarray(out._data), want, atol=2e-5,
                               rtol=2e-5)
    label = ("fallback", "external_mask" if kernel else "disabled")
    assert after[label] - before.get(label, 0) == 1


@pytest.mark.parametrize("kv", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("s", [128, 40], ids=["on_the_grid", "padded"])
def test_flash_prefill_reads_the_prompt_alone_and_counts_nothing(
        s, kv, monkeypatch):
    """At offset 0 of a contiguous cache a caller that asks for it gets
    causal flash attention over the step's own keys; the cache is
    written all the same, and no decode counter moves."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
    rng = np.random.default_rng(9)
    cache = {n: jnp.zeros((1, 256, kv, D), jnp.float32) for n in ("k", "v")}
    with paddle.no_grad():
        q, k, v = _rand(rng, 1, s, H, D), _rand(rng, 1, s, kv, D), \
            _rand(rng, 1, s, kv, D)
        before = _counts()
        out, new_cache = cached_attention(q, k, v, cache, 0, family="llama",
                                          flash_prefill=True)
        assert _counts() == before
    np.testing.assert_array_equal(
        np.asarray(new_cache["k"]._data)[:, :s], np.asarray(k._data))
    visible = np.tril(np.ones((s, s), bool))[None]
    want = _plain_attention(np.asarray(q._data), np.asarray(k._data),
                            np.asarray(v._data), visible)
    np.testing.assert_allclose(np.asarray(out._data), want, atol=2e-5,
                               rtol=2e-5)


def test_no_model_file_decides_a_cache_format_or_a_kernel():
    """An ``ast`` walk of ``paddle_tpu/models``: nothing there imports
    the decode kernels' module, and no cache dict is read by the keys
    that name its layout (subscript, ``.get``, ``in``)."""
    layout_keys = {"bt", "ks", "vs", "tree_mask"}
    found = []
    root = pathlib.Path(paddle.__file__).parent / "models"
    for path in sorted(root.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            where = f"{path.name}:{getattr(node, 'lineno', 0)}"
            if isinstance(node, ast.ImportFrom) and node.module \
                    and "decode_attention" in node.module:
                found.append(f"{where} imports {node.module}")
            if isinstance(node, ast.Import) and any(
                    "decode_attention" in a.name for a in node.names):
                found.append(f"{where} imports decode_attention")
            key = None
            if isinstance(node, ast.Subscript):
                key = node.slice
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "get" and node.args:
                key = node.args[0]
            elif isinstance(node, ast.Compare) and any(
                    isinstance(op, (ast.In, ast.NotIn)) for op in node.ops):
                key = node.left
            if isinstance(key, ast.Constant) and key.value in layout_keys:
                found.append(f"{where} reads a cache by {key.value!r}")
    assert found == []


def test_one_dispatch_decision_is_left():
    assert not hasattr(fd, "paged_decode_dispatch")
    assert "paged" in inspect.signature(fd.decode_dispatch).parameters
