"""Metric arithmetic against hand-computed values."""

import statistics

import numpy as np
import pytest

from perfbench import ops_bytes, stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 99, 100])
def test_percentile_is_numpy_s_linear_rule(q):
    xs = list(np.random.default_rng(0).exponential(size=113))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ten_samples_lie_beyond_p90_of_101_requests():
    assert stats.samples_beyond(101, 90) == 10
    assert stats.samples_beyond(112, 90) == 11
    assert stats.samples_beyond(8824, 99) == 88


def test_spread_uses_python_s_quartiles_not_numpy_s():
    vals = [826.7, 838.9, 829.9, 816.5, 828.5, 831.6]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))
    narrower = (np.percentile(vals, 75) - np.percentile(vals, 25)) \
        / np.median(vals)
    assert stats.spread(vals) > narrower


def test_trimmed_drops_the_one_farthest_from_the_median():
    assert stats.trimmed([10, 11, 12, 13, 30, 12]) == [10, 11, 12, 13, 12]
    assert stats.trimmed([1.0, 5.0, 5.1, 5.2]) == [5.0, 5.1, 5.2]


def test_tokens_are_credited_by_when_they_were_produced():
    # a prompt of 100 tokens prefilled over [8, 12], window [10, 20):
    # half of it falls inside; of its output tokens two do
    prefills = [(8.0, 12.0, 100), (19.0, 21.0, 40), (30.0, 31.0, 7)]
    tokens = [9.5, 12.0, 19.99, 20.0, 25.0]
    assert stats.tokens_in_window(prefills, tokens, 10.0, 20.0) \
        == pytest.approx(50 + 20 + 2)


def test_a_request_finishing_outside_the_window_still_counts_its_part():
    inside = stats.tokens_in_window([(0.0, 10.0, 1000)], [], 2.0, 4.0)
    assert inside == pytest.approx(200)


def test_overlap_of_an_instant():
    assert stats.overlap_share(5.0, 5.0, 0.0, 10.0) == 1.0
    assert stats.overlap_share(15.0, 15.0, 0.0, 10.0) == 0.0


def test_gaps():
    assert stats.gaps([1.0, 1.5, 3.0]) == [0.5, 1.5]
    assert stats.gaps([1.0]) == []


MISTRAL3 = dict(hidden_size=4096, intermediate_size=14336, vocab_size=32000,
                num_attention_heads=32, num_key_value_heads=8,
                num_hidden_layers=3)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_parameters_leave_out_the_embedding_lookup():
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert ops_bytes.matmul_params(MISTRAL3) == 3 * per_layer + 4096 * 32000


def test_train_step_flops_are_six_n_t_plus_causal_attention():
    n = ops_bytes.matmul_params(MISTRAL3)
    attn = 3 * 1 * 3 * 2 * 4096 * 4096 * 4096  # layers x batch x passes x 2 s^2 h
    assert ops_bytes.train_step_flops(MISTRAL3, 1, 4096) \
        == 6 * n * 4096 + attn


def test_mfu_of_a_159_ms_step_at_3_layers_is_about_two_thirds():
    flops = ops_bytes.train_step_flops(MISTRAL3, 1, 4096)
    assert 100 * flops / (0.1592 * 197e12) == pytest.approx(65.5, abs=0.5)


def test_mfu_cannot_pass_100_at_the_peak_itself():
    flops = ops_bytes.train_step_flops(MISTRAL3, 8, 2048)
    least = flops / (4 * 197e12)
    assert 100 * flops / (least * 4 * 197e12) == pytest.approx(100.0)


def test_decode_attention_is_bound_by_bytes():
    gpt = dict(hidden_size=2048, num_attention_heads=16, num_hidden_layers=24)
    flops, nbytes = ops_bytes.decode_attention_cost(gpt, 1000)
    assert nbytes == 24 * 1000 * 2 * 2048 * 2
    assert flops == 24 * 1000 * 4 * 2048
    least, roof = ops_bytes.roofline_seconds(flops, nbytes, V5E)
    assert roof == "bytes" and least == pytest.approx(nbytes / 819e9)


def test_gqa_reads_only_the_kv_heads():
    _, nbytes = ops_bytes.decode_attention_cost(MISTRAL3, 10)
    assert nbytes == 3 * 10 * 2 * 1024 * 2


def test_flash_attention_is_bound_by_flops_at_4k():
    flops, nbytes = ops_bytes.flash_attention_cost(MISTRAL3, 1, 4096)
    assert flops == 3 * 3 * 2 * 4096 ** 3
    _, roof = ops_bytes.roofline_seconds(flops, nbytes, V5E)
    assert roof == "flops"
