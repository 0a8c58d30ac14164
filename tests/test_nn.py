"""nn layer tests (reference patterns: test/legacy_test/test_layers.py,
test_conv2d_op.py, test_layer_norm_op.py, test_cross_entropy_loss.py)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

RNG = np.random.RandomState(3)


def a(*shape):
    return RNG.randn(*shape).astype(np.float32)


class TestLayerBase:
    def test_registration_and_state_dict(self):
        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(4, 8)
                self.fc2 = nn.Linear(8, 2)

            def forward(self, x):
                return self.fc2(self.fc1(x))

        m = M()
        names = [n for n, _ in m.named_parameters()]
        assert names == ["fc1.weight", "fc1.bias", "fc2.weight", "fc2.bias"]
        sd = m.state_dict()
        m2 = M()
        m2.set_state_dict(sd)
        np.testing.assert_allclose(m2.fc1.weight.numpy(), m.fc1.weight.numpy())

    def test_train_eval_modes(self):
        m = nn.Sequential(nn.Linear(2, 2), nn.Dropout(0.5))
        assert m.training
        m.eval()
        assert not m[1].training
        m.train()
        assert m[1].training

    def test_apply_and_children(self):
        m = nn.Sequential(nn.Linear(2, 2), nn.Linear(2, 2))
        count = []
        m.apply(lambda l: count.append(type(l).__name__))
        assert "Linear" in count and "Sequential" in count

    def test_to_dtype(self):
        m = nn.Linear(2, 2)
        m.to(dtype="bfloat16")
        assert str(m.weight.dtype) == "bfloat16"

    def test_forward_hooks(self):
        m = nn.Linear(2, 2)
        calls = []
        h1 = m.register_forward_pre_hook(lambda l, inp: calls.append("pre"))
        h2 = m.register_forward_post_hook(lambda l, inp, out: calls.append("post"))
        m(paddle.randn([1, 2]))
        assert calls == ["pre", "post"]
        h1.remove()
        h2.remove()
        calls.clear()
        m(paddle.randn([1, 2]))
        assert calls == []

    def test_buffers(self):
        m = nn.BatchNorm2D(3)
        bufs = dict(m.named_buffers())
        assert "_mean" in bufs and "_variance" in bufs
        assert "_mean" in m.state_dict()


class TestCommonLayers:
    def test_linear(self):
        layer = nn.Linear(4, 3)
        x = a(2, 4)
        out = layer(paddle.to_tensor(x))
        expected = x @ layer.weight.numpy() + layer.bias.numpy()
        np.testing.assert_allclose(out.numpy(), expected, rtol=1e-5)

    def test_embedding(self):
        emb = nn.Embedding(10, 4, padding_idx=0)
        idx = paddle.to_tensor(np.array([[1, 0], [2, 3]], np.int32))
        out = emb(idx)
        assert out.shape == [2, 2, 4]
        np.testing.assert_allclose(out.numpy()[0, 1], np.zeros(4))

    def test_dropout_train_eval(self):
        paddle.seed(0)
        d = nn.Dropout(0.5)
        x = paddle.ones([100, 100])
        out = d(x)
        kept = (out.numpy() != 0).mean()
        assert 0.4 < kept < 0.6
        np.testing.assert_allclose(out.numpy()[out.numpy() != 0], 2.0)  # upscale_in_train
        d.eval()
        np.testing.assert_allclose(d(x).numpy(), x.numpy())

    def test_activations(self):
        x = a(3, 4)
        np.testing.assert_allclose(nn.ReLU()(paddle.to_tensor(x)).numpy(), np.maximum(x, 0))
        np.testing.assert_allclose(nn.LeakyReLU(0.1)(paddle.to_tensor(x)).numpy(),
                                   np.where(x > 0, x, 0.1 * x), rtol=1e-6)
        s = nn.Softmax(-1)(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(s.sum(-1), np.ones(3), rtol=1e-6)

    def test_gelu(self):
        from scipy.stats import norm

        x = a(3, 4)
        expected = x * norm.cdf(x)
        np.testing.assert_allclose(F.gelu(paddle.to_tensor(x)).numpy(), expected, atol=1e-5)


class TestConvPool:
    def test_conv2d_identity(self):
        conv = nn.Conv2D(1, 1, 1, bias_attr=False)
        conv.weight.set_value(np.ones((1, 1, 1, 1), np.float32))
        x = a(1, 1, 4, 4)
        np.testing.assert_allclose(conv(paddle.to_tensor(x)).numpy(), x, rtol=1e-6)

    def test_conv2d_vs_manual(self):
        conv = nn.Conv2D(2, 3, 3, padding=1)
        x = a(2, 2, 5, 5)
        out = conv(paddle.to_tensor(x))
        assert out.shape == [2, 3, 5, 5]
        # cross-check one output position against direct correlation
        w = conv.weight.numpy()
        b = conv.bias.numpy()
        xp = np.pad(x, [(0, 0), (0, 0), (1, 1), (1, 1)])
        manual = (xp[0, :, 1:4, 1:4] * w[1]).sum() + b[1]
        np.testing.assert_allclose(out.numpy()[0, 1, 1, 1], manual, rtol=1e-4)

    def test_conv2d_stride_groups(self):
        conv = nn.Conv2D(4, 4, 3, stride=2, padding=1, groups=2)
        out = conv(paddle.to_tensor(a(1, 4, 8, 8)))
        assert out.shape == [1, 4, 4, 4]

    def test_conv2d_transpose(self):
        deconv = nn.Conv2DTranspose(2, 3, 4, stride=2, padding=1)
        out = deconv(paddle.to_tensor(a(1, 2, 5, 5)))
        assert out.shape == [1, 3, 10, 10]

    def test_pools(self):
        x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        mp = F.max_pool2d(paddle.to_tensor(x), 2, 2).numpy()
        np.testing.assert_allclose(mp[0, 0], [[5, 7], [13, 15]])
        ap = F.avg_pool2d(paddle.to_tensor(x), 2, 2).numpy()
        np.testing.assert_allclose(ap[0, 0], [[2.5, 4.5], [10.5, 12.5]])

    def test_adaptive_pool(self):
        x = a(2, 3, 8, 8)
        out = F.adaptive_avg_pool2d(paddle.to_tensor(x), 1).numpy()
        np.testing.assert_allclose(out[..., 0, 0], x.mean((2, 3)), rtol=1e-5)


class TestNorms:
    def test_layer_norm(self):
        ln = nn.LayerNorm(8)
        x = a(4, 8)
        out = ln(paddle.to_tensor(x)).numpy()
        mu = x.mean(-1, keepdims=True)
        sd = x.std(-1, keepdims=True)
        np.testing.assert_allclose(out, (x - mu) / np.sqrt(sd**2 + 1e-5), rtol=1e-4, atol=1e-5)

    def test_rms_norm(self):
        rn = nn.RMSNorm(8)
        x = a(4, 8)
        out = rn(paddle.to_tensor(x)).numpy()
        expected = x / np.sqrt((x**2).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)

    def test_batch_norm_train_and_eval(self):
        bn = nn.BatchNorm2D(3, momentum=0.9)
        x = a(4, 3, 5, 5) * 2 + 1
        out = bn(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out.mean((0, 2, 3)), np.zeros(3), atol=1e-5)
        np.testing.assert_allclose(out.std((0, 2, 3)), np.ones(3), atol=1e-3)
        # running stats updated
        assert not np.allclose(bn._mean.numpy(), np.zeros(3))
        bn.eval()
        out_eval = bn(paddle.to_tensor(x)).numpy()
        expected = (x - bn._mean.numpy()[None, :, None, None]) / np.sqrt(
            bn._variance.numpy()[None, :, None, None] + 1e-5)
        np.testing.assert_allclose(out_eval, expected * bn.weight.numpy()[None, :, None, None]
                                   + bn.bias.numpy()[None, :, None, None], rtol=1e-4, atol=1e-4)

    def test_batch_norm_bf16_single_pass_stats_tolerance(self):
        """Documents the ACCEPTED numerics of the half-precision training
        path (nn/functional.py _bn_train_fwd): bf16 inputs use single-pass
        statistics in fp32 — one read of x instead of two on a
        bandwidth-bound step — taken about a pivot, each channel's first
        element. The contract is the two-pass form's variance to 5e-4 at
        mean/std = 10 and 6e-2 at 100. A numerics regression (e.g.
        accidentally computing the moments in bf16, which reads 0.5 rel
        err at ratio 10 and 1.0 at 100) is caught here instead of
        silently shifting training curves.

        Without the pivot E[x^2]-E[x]^2 loses (mean/std)^2 times the
        error of the backend's sum of 16,384 float32 squares, which is
        1e-5 to 1e-4 on this installation's CPU (JAX 0.9.0, 2026-09):
        8e-4 to 9e-4 at ratio 10 and 0.13 to 0.14 at ratio 100 over
        three seeds, over both bounds, which is how this test failed on
        every run from the seed to PR 28. With it the loss is (the
        pivot's distance in std)^2 times that error whatever the mean:
        6.8e-6 and 1.4e-7 on this data; over three seeds and ratios 10,
        100 and 1000 at most 6.4e-4, in a channel whose pivot sits 2.2
        std out."""
        import jax.numpy as jnp

        from paddle_tpu.nn.functional import _bn_train_fwd

        rng = np.random.RandomState(0)
        for mean, bound in ((10.0, 5e-4), (100.0, 6e-2)):
            x64 = rng.randn(64, 8, 16, 16) + mean  # std ~1 per channel
            x = jnp.asarray(x64, jnp.bfloat16)
            _, (_, m, r, _, _) = _bn_train_fwd(x, None, None, (0, 2, 3), 1e-5)
            var_single = 1.0 / np.asarray(r, np.float64) ** 2 - 1e-5
            # oracle: two-pass moments of the SAME bf16-rounded values, f64
            xf = np.asarray(x.astype(jnp.float32), np.float64)
            var_two_pass = xf.var(axis=(0, 2, 3), keepdims=True)
            rel = np.abs(var_single - var_two_pass) / var_two_pass
            assert rel.max() < bound, (
                f"single-pass bf16 BN variance drifted {rel.max():.3e} from "
                f"the two-pass oracle at mean/std={mean:.0f} — exceeds the "
                f"documented {bound:.0e} tolerance")
            # and the mean itself is exact to bf16 resolution
            np.testing.assert_allclose(np.asarray(m, np.float64).ravel(),
                                       xf.mean(axis=(0, 2, 3)).ravel(),
                                       rtol=2e-3)

    def test_group_norm(self):
        gn = nn.GroupNorm(2, 4)
        x = a(2, 4, 3, 3)
        out = gn(paddle.to_tensor(x)).numpy()
        g = x.reshape(2, 2, 2, 3, 3)
        mu = g.mean((2, 3, 4), keepdims=True)
        var = g.var((2, 3, 4), keepdims=True)
        expected = ((g - mu) / np.sqrt(var + 1e-5)).reshape(2, 4, 3, 3)
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


class TestLosses:
    def test_cross_entropy(self):
        logits = a(4, 5)
        labels = np.array([0, 2, 4, 1], np.int64)
        loss = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels)).numpy()
        # manual
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        expected = -np.log(p[np.arange(4), labels]).mean()
        np.testing.assert_allclose(loss, expected, rtol=1e-5)

    def test_cross_entropy_ignore_index(self):
        logits = a(4, 5)
        labels = np.array([0, -100, 4, -100], np.int64)
        loss = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels)).numpy()
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        expected = -(np.log(p[0, 0]) + np.log(p[2, 4])) / 2
        np.testing.assert_allclose(loss, expected, rtol=1e-5)

    def test_cross_entropy_soft_label(self):
        logits = a(3, 4)
        soft = np.abs(a(3, 4))
        soft = soft / soft.sum(-1, keepdims=True)
        loss = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(soft), soft_label=True).numpy()
        logp = logits - logits.max(-1, keepdims=True)
        logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
        np.testing.assert_allclose(loss, -(soft * logp).sum(-1).mean(), rtol=1e-5)

    def test_mse_l1(self):
        x, y = a(3, 4), a(3, 4)
        np.testing.assert_allclose(F.mse_loss(paddle.to_tensor(x), paddle.to_tensor(y)).numpy(),
                                   ((x - y) ** 2).mean(), rtol=1e-6)
        np.testing.assert_allclose(F.l1_loss(paddle.to_tensor(x), paddle.to_tensor(y)).numpy(),
                                   np.abs(x - y).mean(), rtol=1e-6)

    def test_bce(self):
        p = 1 / (1 + np.exp(-a(4, 3)))
        y = (a(4, 3) > 0).astype(np.float32)
        out = F.binary_cross_entropy(paddle.to_tensor(p), paddle.to_tensor(y)).numpy()
        expected = -(y * np.log(p) + (1 - y) * np.log(1 - p)).mean()
        np.testing.assert_allclose(out, expected, rtol=1e-5)

    def test_kl_div(self):
        logq = np.log(np.abs(a(3, 4)) + 0.5)
        p = np.abs(a(3, 4)) + 0.1
        out = F.kl_div(paddle.to_tensor(logq), paddle.to_tensor(p), reduction="sum").numpy()
        np.testing.assert_allclose(out, (p * (np.log(p) - logq)).sum(), rtol=1e-4)


class TestAttention:
    def test_sdpa_matches_manual(self):
        b, s, h, d = 2, 5, 2, 4
        q, k, v = a(b, s, h, d), a(b, s, h, d), a(b, s, h, d)
        out = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v)).numpy()
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        scores = qt @ kt.transpose(0, 1, 3, 2) / np.sqrt(d)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        probs = e / e.sum(-1, keepdims=True)
        expected = (probs @ vt).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)

    def test_sdpa_causal(self):
        b, s, h, d = 1, 4, 1, 2
        q, k, v = a(b, s, h, d), a(b, s, h, d), a(b, s, h, d)
        out = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v), is_causal=True).numpy()
        # first position attends only to itself
        np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], rtol=1e-5)

    def test_multihead_attention(self):
        mha = nn.MultiHeadAttention(8, 2)
        x = paddle.to_tensor(a(2, 5, 8))
        out = mha(x)
        assert out.shape == [2, 5, 8]

    def test_transformer_encoder(self):
        layer = nn.TransformerEncoderLayer(16, 4, 32)
        enc = nn.TransformerEncoder(layer, 2)
        out = enc(paddle.to_tensor(a(2, 6, 16)))
        assert out.shape == [2, 6, 16]
        # distinct layers (deepcopy, not shared)
        p0 = enc.layers[0].linear1.weight
        p1 = enc.layers[1].linear1.weight
        assert p0 is not p1


class TestClip:
    def test_clip_by_global_norm(self):
        clip = nn.ClipGradByGlobalNorm(1.0)
        p1 = paddle.Parameter(np.zeros(3, np.float32))
        p2 = paddle.Parameter(np.zeros(3, np.float32))
        g1 = paddle.to_tensor(np.array([3.0, 0, 0], np.float32))
        g2 = paddle.to_tensor(np.array([0, 4.0, 0], np.float32))
        out = clip([(p1, g1), (p2, g2)])
        total = np.sqrt(sum((g.numpy() ** 2).sum() for _, g in out))
        np.testing.assert_allclose(total, 1.0, rtol=1e-5)

    def test_clip_by_value(self):
        clip = nn.ClipGradByValue(0.5)
        p = paddle.Parameter(np.zeros(2, np.float32))
        g = paddle.to_tensor(np.array([2.0, -2.0], np.float32))
        (_, gg), = clip([(p, g)])
        np.testing.assert_allclose(gg.numpy(), [0.5, -0.5])


class TestTransformerDecodeCache:
    """Incremental-decode caches (reference transformer.py Cache/
    StaticCache/gen_cache). Oracle: token-by-token cached decoding must
    reproduce the full causal forward exactly."""

    def _causal(self, s):
        m = np.triu(np.full((s, s), -1e9, np.float32), k=1)
        return paddle.to_tensor(m[None, None])

    def test_mha_cache_matches_full_forward(self):
        paddle.seed(0)
        mha = nn.MultiHeadAttention(16, 4)
        mha.eval()
        x = paddle.to_tensor(np.random.RandomState(0).randn(2, 5, 16).astype(np.float32))
        full = mha(x, x, x, attn_mask=self._causal(5)).numpy()
        cache = mha.gen_cache(x[:, :0])
        outs = []
        for t in range(5):
            step = x[:, t:t + 1]
            o, cache = mha(step, step, step, cache=cache)
            outs.append(o.numpy())
        np.testing.assert_allclose(np.concatenate(outs, 1), full,
                                   rtol=1e-5, atol=1e-6)

    def test_encoder_layer_cache_matches_full(self):
        paddle.seed(1)
        layer = nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0)
        layer.eval()
        x = paddle.to_tensor(np.random.RandomState(1).randn(1, 4, 16).astype(np.float32))
        full = layer(x, src_mask=self._causal(4)).numpy()
        cache = layer.gen_cache(x[:, :0])
        outs = []
        for t in range(4):
            o, cache = layer(x[:, t:t + 1], cache=cache)
            outs.append(o.numpy())
        np.testing.assert_allclose(np.concatenate(outs, 1), full,
                                   rtol=1e-5, atol=1e-6)

    def test_decoder_cached_matches_full(self):
        paddle.seed(2)
        dec_layer = nn.TransformerDecoderLayer(16, 4, 32, dropout=0.0)
        dec = nn.TransformerDecoder(dec_layer, 2)
        dec.eval()
        rng = np.random.RandomState(2)
        memory = paddle.to_tensor(rng.randn(1, 6, 16).astype(np.float32))
        tgt = paddle.to_tensor(rng.randn(1, 4, 16).astype(np.float32))
        full = dec(tgt, memory, tgt_mask=self._causal(4)).numpy()
        caches = dec.gen_cache(memory)
        # StaticCache precomputes the encoder k/v once
        from paddle_tpu.nn import MultiHeadAttention
        assert isinstance(caches[0][1], MultiHeadAttention.StaticCache)
        outs = []
        for t in range(4):
            o, caches = dec(tgt[:, t:t + 1], memory, cache=caches)
            outs.append(o.numpy())
        np.testing.assert_allclose(np.concatenate(outs, 1), full,
                                   rtol=1e-5, atol=1e-6)
