"""EvaByte (EVA chunked linearized attention) against its plain
reference, ``perfbench/references/evabyte.py``: the uncached forward on
every prediction head, chunked prefill and decode through the paged
cache across window boundaries, the engine's windowed block
bookkeeping, preemption, and what the engine refuses to combine with
it. Float32 at tiny sizes on seeded weights, compared on logits.

Tolerance: 2e-5 absolute on logits of order 1. Program and reference
are both float32 and differ only in the order of their sums (a softmax
over [summaries; window] against a gathered table, einsum contractions
of other shapes), which reads 1e-6 or less here; computing in bfloat16
moves a logit by 1e-2, a dropped ``mu`` or unit offset or a uniform
chunk mean by 1e-2 and more (the mutation tests hold that).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.generation import eva_virtual_position
from paddle_tpu.models import EvaByteConfig, EvaByteForCausalLM
from paddle_tpu.models.evabyte import eva_attention
from paddle_tpu.nn import functional as F
from paddle_tpu.serving.block_pool import WindowedLayout
from perfbench import weights
from perfbench.programs import install_weights
from perfbench.references import evabyte as ref

TOL = 2e-5
W, C = 64, 16
SIZES = dict(vocab_size=96, hidden_size=128, intermediate_size=256,
             num_hidden_layers=2, num_attention_heads=4,
             max_position_embeddings=512, chunk_size=C, window_size=W,
             num_pred_heads=8, rms_norm_eps=1e-5, rope_theta=100000.0)


def build(seed=11, dtype="float32"):
    """(model, reference parameters) on the same seeded leaves."""
    spec = ref.param_spec(SIZES)
    model = EvaByteForCausalLM(EvaByteConfig(
        num_key_value_heads=SIZES["num_attention_heads"], dtype=dtype,
        **SIZES))
    install_weights(model, spec, weights.make(spec, seed, jnp.dtype(dtype)))
    return model, weights.make(spec, seed, jnp.dtype(dtype),
                               upcast=jnp.float32)


@pytest.fixture(scope="module")
def pair():
    return build()


def tokens(n, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=n).astype(np.int32)


def gap(model, params, ids):
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data[0])
    want = np.asarray(ref.all_head_logits(params, jnp.asarray(ids), SIZES))
    assert got.shape == want.shape == (
        len(ids), SIZES["num_pred_heads"], SIZES["vocab_size"])
    return float(np.abs(got - want).max())


@pytest.mark.parametrize("n", [1, W - 1, W, W + 1, 3 * W + W // 2])
def test_uncached_forward_matches_the_reference_on_every_head(pair, n):
    model, params = pair
    assert gap(model, params, tokens(n)) < TOL


def _no_mu(model):
    for layer in model.evabyte.layers:
        p = layer.self_attn.adaptive_mu_k
        p._data = jnp.zeros_like(p._data)


def _uniform_chunk_mean(model):
    for layer in model.evabyte.layers:
        p = layer.self_attn.adaptive_phi
        p._data = jnp.zeros_like(p._data)


def _no_unit_offset(model):
    # N(x) * w in place of N(x) * (1 + w)
    for name, p in model.named_parameters_dict().items():
        if name.endswith("norm.weight") or "layernorm" in name:
            p._data = p._data - 1.0


@pytest.mark.parametrize("break_it", [_no_mu, _uniform_chunk_mean,
                                      _no_unit_offset])
def test_a_part_of_the_mathematics_left_out_fails_the_tolerance(break_it):
    model, params = build()
    break_it(model)
    assert gap(model, params, tokens(3 * W + W // 2)) > 50 * TOL


def test_bfloat16_in_a_float32_test_fails_the_tolerance():
    model, params = build(dtype="bfloat16")
    assert gap(model, params, tokens(3 * W + W // 2)) > 50 * TOL


def _qkv(n, heads=4, d=32, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(1, n, heads, d)).astype(np.float32)
            for _ in range(3)]


def test_phi_and_mu_zero_is_chunk_mean_pooling():
    """With ``phi`` = 0 and ``mu`` = 0 a summary is the plain mean of
    its chunk: EVA attention then equals a softmax over the window's
    keys and the chunk means of every window behind, written out here
    row by row."""
    n = 3 * W + 5
    q, k, v = _qkv(n)
    zero = paddle.to_tensor(np.zeros((4, 32), np.float32))
    got = np.asarray(eva_attention(*(paddle.to_tensor(t) for t in (q, k, v)),
                                   zero, zero, W, C)._data)[0]
    want = np.zeros_like(got)
    for i in range(n):
        at = i // W * W
        keys = [k[0, m * C:(m + 1) * C].mean(0) for m in range(at // C)] \
            + list(k[0, at:i + 1])
        vals = [v[0, m * C:(m + 1) * C].mean(0) for m in range(at // C)] \
            + list(v[0, at:i + 1])
        sc = np.einsum("hd,khd->hk", q[0, i], np.stack(keys)) / np.sqrt(32)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want[i] = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True),
                            np.stack(vals))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_under_one_window_it_is_causal_softmax_attention():
    q, k, v = (paddle.to_tensor(t) for t in _qkv(W - 3))
    rng = np.random.default_rng(9)
    phi, mu = (paddle.to_tensor(rng.normal(size=(4, 32)).astype(np.float32))
               for _ in range(2))
    got = eva_attention(q, k, v, phi, mu, W, C)._data
    want = F.scaled_dot_product_attention(q, k, v, is_causal=True)._data
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


# -- through the paged cache --------------------------------------------------

def engine_for(model, slots=1, max_len=320, num_blocks=None, chunk=32):
    return serving.ServingEngine(model, serving.ServingConfig(
        max_slots=slots, max_len=max_len, block_size=4, prefill_chunk=chunk,
        prefix_caching=False, num_blocks=num_blocks))


def served_logits(model, ids, prefill_len, chunk=32):
    """Head 0's logits at every position of ``ids``: the first
    ``prefill_len`` through prefill chunks, the rest one decode step at a
    time, on the engine's own pools, table and block bookkeeping."""
    eng = engine_for(model, chunk=chunk)
    run, pb = eng._run, eng._pb

    @jax.jit
    def chunk_fn(pools, bt, toks, pos0, valid):
        return run(pb, toks, [dict(c, bt=bt, valid=valid[None])
                              for c in pools], pos0)

    @jax.jit
    def step_fn(pools, bt, tok, pos):
        return run(pb, tok, [dict(c, bt=bt) for c in pools], pos)

    pools, out = eng._pools, []
    for a in range(0, prefill_len, chunk):
        b = min(a + chunk, prefill_len)
        eng._reserve_write(0, a, b)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :b - a] = ids[a:b]
        lg, pools = chunk_fn(pools, jnp.asarray(eng._bt), jnp.asarray(toks),
                             jnp.asarray(a, jnp.int32),
                             jnp.asarray(b - a, jnp.int32))
        out.append(np.asarray(lg[0, :b - a]))
    for p in range(prefill_len, len(ids)):
        eng._reserve_write(0, p, p + 1)
        lg, pools = step_fn(pools, jnp.asarray(eng._bt),
                            jnp.asarray(ids[p:p + 1])[None],
                            jnp.asarray([p], jnp.int32))
        out.append(np.asarray(lg[:, 0]))
    return np.concatenate(out), eng


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("prefill_len", [W + 20, 3 * W + 7])
def test_prefill_in_chunks_then_decode_matches_the_reference(
        pair, monkeypatch, prefill_len, kernel):
    """The program's cache (exact keys of the window, summaries pooled
    as chunks fill, the window rolled by the host) against the
    reference's forward over the whole sequence. A short prefill leaves
    whole windows to the decode step and chunks that a prefill began
    for a step to finish; a long one rolls inside the prefill. With the
    paged kernel (interpreted here) and with the XLA gather path."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE",
                       "1" if kernel == "pallas" else "0")
    model, params = pair
    n = 3 * W + W // 2 if kernel == "xla" else prefill_len + 12
    ids = tokens(n, seed=prefill_len)
    got, eng = served_logits(model, ids, prefill_len)
    want = np.asarray(ref.logit_rows(params, jnp.asarray(ids), 0, 256, SIZES))
    assert got.shape == want.shape
    assert float(np.abs(got - want).max()) < TOL
    c = eng.counters()
    assert c["window_rolls"] == (n - 1) // W
    assert c["summary_entries_written"] == n // C


def test_a_resume_by_recompute_gives_the_same_logits(pair):
    """Preemption folds the generated tokens into a new prefill: the
    positions a decode step wrote and pooled first are then written and
    pooled by prefill chunks, to the same logits."""
    model, _ = pair
    ids = tokens(2 * W + 40, seed=21)
    first, _ = served_logits(model, ids, W + 9)
    again, _ = served_logits(model, ids, 2 * W + 25)
    assert float(np.abs(first - again).max()) < TOL


def is_greedy(model, prompt, out):
    """One uncached forward over prompt and answer: every emitted token
    is head 0's best at the position before it."""
    ids = np.concatenate([prompt, np.asarray(out, np.int32)])
    lg = model(paddle.to_tensor(ids[None]))._data[0, :, 0]
    best = np.asarray(jnp.argmax(lg, -1))[len(prompt) - 1:-1]
    return best.tolist() == list(out)


def test_the_engine_rolls_windows_and_gives_every_block_back(pair):
    model, _ = pair
    eng = engine_for(model, slots=2)
    free0 = eng.pool.free_blocks
    lay = eng._layout
    prompts = [tokens(2 * W + 11, seed=31), tokens(W - 5, seed=32)]
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    held = []
    while eng.step():
        held.append(eng.pool.usable_blocks - eng.pool.free_blocks)
    for p, r in zip(prompts, reqs):
        assert len(r.output_tokens) == 24
        assert is_greedy(model, p, r.output_tokens)
    # two boundaries in the first prefill, one in the second decode; a roll
    # releases the window's W / block_size blocks and nothing else
    c = eng.counters()
    assert c["window_rolls"] == 3
    assert c["window_blocks_released"] == 3 * (W // 4)
    assert c["summary_entries_written"] == (2 * W + 11 + 23) // C \
        + (W - 5 + 23) // C
    assert max(held) <= lay.peak(2 * W + 35) + lay.peak(W + 19)
    assert eng.pool.free_blocks == free0
    assert c["preemptions"] == 0


def test_preemption_and_resume_emit_the_same_tokens(pair):
    """A pool that cannot hold both requests at their peaks: the later
    one is preempted, requeued, and recomputed over exact keys and
    summaries alike; nothing is delivered twice."""
    model, _ = pair
    lay = WindowedLayout(4, W, C, 320)
    prompts = [tokens(W + 40, seed=41), tokens(W + 30, seed=42)]
    n_new = 40
    need = [lay.peak(len(p) + n_new) for p in prompts]
    eng = engine_for(model, slots=2, num_blocks=1 + max(need) + need[1] // 2)
    reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.run_until_idle()
    assert eng.counters()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.output_tokens) == n_new
        assert is_greedy(model, p, r.output_tokens)
    assert eng.pool.free_blocks == eng.pool.usable_blocks


REFUSED = {
    "prefix_caching": dict(prefix_caching=True),
    "kv_tier": dict(prefix_caching=True, kv_tier=True),
    "tp2": dict(prefix_caching=False, tp=2),
    "int8_pool": dict(prefix_caching=False, kv_format="int8"),
    "chunk_not_of_whole_chunks": dict(prefix_caching=False, prefill_chunk=24),
    "chunk_across_windows": dict(prefix_caching=False, prefill_chunk=48),
}


@pytest.mark.parametrize("options", REFUSED.values(), ids=REFUSED.keys())
def test_what_assumes_every_exact_key_is_refused_with_a_sentence(
        pair, options):
    with pytest.raises(ValueError, match="EVA model|prefill_chunk"):
        serving.ServingEngine(pair[0], serving.ServingConfig(
            max_slots=1, max_len=256, block_size=4, **options))


def test_a_draft_model_is_refused_with_a_sentence(pair):
    with pytest.raises(ValueError, match="EVA model.*draft"):
        serving.ServingEngine(
            pair[0], serving.ServingConfig(
                max_slots=1, max_len=256, block_size=4, prefix_caching=False),
            draft_model=pair[0])


def test_a_block_size_that_splits_a_windows_summaries_is_refused(pair):
    with pytest.raises(ValueError, match="summaries must fill whole blocks"):
        serving.ServingEngine(pair[0], serving.ServingConfig(
            max_slots=1, max_len=256, block_size=16, prefix_caching=False))


# -- the layout's arithmetic --------------------------------------------------

@pytest.mark.parametrize("bs,window,chunk", [(4, 64, 16), (16, 2048, 16)])
def test_layout_counts_against_a_walk_over_every_position(bs, window, chunk):
    """Write position after position, rolling as the engine does, and
    count the table entries in use: ``held``, ``peak`` and
    ``read_blocks`` are closed forms of that walk, and the device's
    virtual position is the entry the walk writes."""
    n = 2 * window + window // 2 + 3
    lay = WindowedLayout(bs, window, chunk, 4 * window)
    row, win, most = set(), 0, 0
    for p in range(n):
        if p // window > win:
            lo = win * lay.per_window
            trailing = {e for e in row if e >= lo + lay.window_blocks}
            row = {e for e in row if e < lo} | {
                e - lay.window_blocks for e in trailing}
            win += 1
        got = lay.entries(p, p + 1)
        assert eva_virtual_position(p, window, chunk) // bs == got[0]
        row |= set(got)
        most = max(most, len(row))
        assert len(row) == lay.held(p + 1)
        assert most == lay.peak(p + 1)
        assert lay.read_blocks(p + 1) == (
            -(-(p % window + 1) // bs), p // window * lay.per_window)
        assert max(row) < lay.width
