"""Extended op schemas: the long tail of the dispatch surface.

Round-4 expansion closing the reference-parity gap (ops.yaml covers every
op that dispatches — paddle/phi/ops/yaml/ops.yaml 467 + backward.yaml 337;
test/legacy_test/op_test.py:2139,3129 sweeps each per dtype/grad). This
module brings the schema registry to the full apply_op surface enumerated
by ops.audit; tests/test_schema_enforcement.py fails on any op that
dispatches without a schema or an explicit NO_SCHEMA_WHITE_LIST entry.

Split from schemas.py purely for file size; imported at the end of
schemas.py so ``SCHEMAS`` is always complete.  Torch (CPU) serves as the
oracle for the nn families the reference validates against cuDNN — the
same oracle discipline as tests/test_torch_oracle.py, but under the
dtype-sweep/FD-grad harness.
"""

from __future__ import annotations

import numpy as np

from .schemas import _DOMAINS, _S, SCHEMAS, WHITE_LIST, sp

# ---------------------------------------------------------------------------
# extra input domains
# ---------------------------------------------------------------------------
_DOMAINS.update({
    # sorted segment ids covering 0..2 with every segment non-empty
    # (segment-op refs reduce per segment; an empty segment has no max/min)
    "segsorted": lambda rng, sh: np.sort(np.concatenate(
        [np.arange(3), rng.randint(0, 3, int(np.prod(sh)) - 3)])
        .astype(np.int32)).reshape(sh),
    "idx2": lambda rng, sh: rng.randint(0, 2, sh).astype(np.int32),
    "binary": lambda rng, sh: rng.randint(0, 2, sh).astype(np.float32),
    # floats away from powers of two (frexp boundaries)
    "pow2safe": lambda rng, sh: (2.0 ** rng.randint(-2, 3, sh)
                                 * rng.uniform(1.1, 1.9, sh)).astype(np.float32),
    # {-1, +1} labels (hinge/margin losses)
    "pm1": lambda rng, sh: (2.0 * rng.randint(0, 2, sh) - 1.0)
    .astype(np.float32),
    # distinct flat indices into a 16-slot plane (max_unpool scatter)
    "dperm16": lambda rng, sh: rng.choice(
        16, size=int(np.prod(sh)), replace=False)
    .astype(np.int32).reshape(sh),
})


# torch is a TEST-oracle dependency only (CPU build): every reference
# below imports it function-locally so importing paddle_tpu never
# requires torch.


def _t(x):
    import torch as _torch

    return _torch.from_numpy(np.ascontiguousarray(x))


def _tn(res):
    if isinstance(res, (tuple, list)):
        return tuple(_tn(r) for r in res)
    return res.detach().numpy()


_SH = (3, 4)
_U = [(_SH, "any")]

# ---------------------------------------------------------------------------
# manipulation: gather/scatter/slice family (reference ops.yaml gather_nd,
# scatter, scatter_nd_add, slice, strided_slice, crop, index_* ...)
# ---------------------------------------------------------------------------
_S("gather_nd",
   lambda x, idx: x[tuple(idx[..., k] for k in range(idx.shape[-1]))],
   [(_SH, "any"), ((2, 2), "idx3")], grad_inputs=[0])


def _scatter_ref(x, idx, upd):
    out = x.copy()
    out[idx] = upd
    return out


_S("scatter", _scatter_ref,
   [(_SH, "any"), ((2,), "idx3"), ((2, 4), "any")], grad_inputs=[0, 2],
   kwargs={"overwrite": True},
   wrap=lambda api: lambda x, i, u, **kw: api(x, i, u, **kw))


def _scatter_nd_add_ref(x, idx, upd):
    out = x.copy().astype(np.float64)
    np.add.at(out, tuple(idx[..., k] for k in range(idx.shape[-1])), upd)
    return out.astype(x.dtype)


_S("scatter_nd_add", _scatter_nd_add_ref,
   [(_SH, "any"), ((2, 1), "idx3"), ((2, 4), "any")], grad_inputs=[0, 2])


def _scatter_nd_ref(idx, upd):
    out = np.zeros((3, 4), np.float64)
    np.add.at(out, tuple(idx[..., k] for k in range(idx.shape[-1])), upd)
    return out.astype(upd.dtype)


_S("scatter_nd", _scatter_nd_ref,
   [((2, 1), "idx3"), ((2, 4), "any")], kwargs={"shape": [3, 4]},
   grad_inputs=[1])

_S("slice", lambda x: x[0:2, 1:3], _U,
   kwargs={"axes": [0, 1], "starts": [0, 1], "ends": [2, 3]})
_S("strided_slice", lambda x: x[0:3:2, 0:4:2], _U,
   kwargs={"axes": [0, 1], "starts": [0, 0], "ends": [3, 4],
           "strides": [2, 2]})
_S("crop", lambda x: x[1:3, 1:3], _U,
   kwargs={"shape": [2, 2], "offsets": [1, 1]})


def _index_add_ref(x, idx, val):
    out = x.copy().astype(np.float64)
    np.add.at(out, idx, val)
    return out.astype(x.dtype)


_S("index_add", _index_add_ref,
   [(_SH, "any"), ((2,), "idx3"), ((2, 4), "any")],
   kwargs={"axis": 0}, grad_inputs=[0, 2],
   wrap=lambda api: lambda x, i, v, axis: api(x, i, axis, v))


def _index_put_ref(x, i0, i1, val):
    out = x.copy()
    out[i0, i1] = val
    return out


_S("index_put", _index_put_ref,
   [(_SH, "any"), ((2,), "idx3"), ((2,), "idx3"), ((2,), "any")],
   grad_inputs=[0, 3],
   wrap=lambda api: lambda x, i0, i1, v: api(x, (i0, i1), v))


def _put_along_axis_ref(x, idx, val):
    out = x.copy()
    np.put_along_axis(out, idx, val, axis=1)
    return out


_S("put_along_axis", _put_along_axis_ref,
   [(_SH, "any"), ((3, 2), "idx3"), ((3, 2), "any")],
   kwargs={"axis": 1, "broadcast": False}, grad_inputs=[0, 2])


def _select_scatter_ref(x, v):
    out = x.copy()
    out[1] = v
    return out


_S("select_scatter", _select_scatter_ref, [(_SH, "any"), ((4,), "any")],
   kwargs={"axis": 0, "index": 1})


def _slice_scatter_ref(x, v):
    out = x.copy()
    out[0:2] = v
    return out


_S("slice_scatter", _slice_scatter_ref, [(_SH, "any"), ((2, 4), "any")],
   kwargs={"axes": [0], "starts": [0], "ends": [2], "strides": [1]})


def _masked_scatter_ref(x, mask, val):
    out = x.copy()
    out[mask] = val.ravel()[:int(mask.sum())]
    return out


_S("masked_scatter", _masked_scatter_ref,
   [(_SH, "any"), (_SH, "bool"), ((12,), "any")], grad=False)

_S("take", lambda x, i: np.take(x, i), [(_SH, "any"), ((2, 3), "idx3")],
   grad_inputs=[0])
_S("isin", np.isin, [(_SH, "int"), ((5,), "int")], dtypes=("int32",),
   grad=False)


def _index_fill_ref(x, idx):
    out = x.copy()
    out[idx] = 0.5
    return out


_S("index_fill", _index_fill_ref, [(_SH, "any"), ((2,), "idx3")],
   kwargs={"axis": 0, "value": 0.5}, grad_inputs=[0])

_S("tensor_split", lambda x: tuple(np.array_split(x, 2, axis=0)), _U,
   kwargs={"num_or_indices": 2})
_S("hsplit", lambda x: tuple(np.array_split(x, 2, axis=1)), _U,
   kwargs={"num_or_indices": 2})
_S("vsplit", lambda x: tuple(np.array_split(x, 3, axis=0)), [((3, 4), "any")],
   kwargs={"num_or_indices": 3})
_S("dsplit", lambda x: tuple(np.array_split(x, 2, axis=2)),
   [((2, 3, 4), "any")], kwargs={"num_or_indices": 2})
_S("unflatten", lambda x: x.reshape(3, 2, 2), _U,
   kwargs={"axis": 1, "shape": [2, 2]})


def _as_strided_ref(x):
    flat = x.ravel()
    out = np.empty((2, 6), x.dtype)
    for i in range(2):
        for j in range(6):
            out[i, j] = flat[1 + i * 4 + j]
    return out


_S("as_strided", _as_strided_ref, [((12,), "any")],
   kwargs={"shape": [2, 6], "stride": [4, 1], "offset": 1})

_S("reverse", lambda x: np.flip(x, 0), _U, kwargs={"axis": [0]})
_S("atleast_1d", np.atleast_1d, _U)
_S("atleast_2d", np.atleast_2d, _U)
_S("atleast_3d", np.atleast_3d, _U)
_S("broadcast_tensors",
   lambda a, b: tuple(np.broadcast_arrays(a, b)),
   [((3, 1), "any"), ((1, 4), "any")],
   wrap=lambda api: lambda a, b: tuple(api([a, b])))
_S("meshgrid", lambda a, b: tuple(np.meshgrid(a, b, indexing="ij")),
   [((3,), "any"), ((4,), "any")],
   wrap=lambda api: lambda a, b: tuple(api(a, b)))


def _cartesian_prod_ref(a, b):
    return np.array([[x, y] for x in a for y in b], a.dtype)


_S("cartesian_prod", _cartesian_prod_ref, [((3,), "any"), ((2,), "any")],
   wrap=lambda api: lambda a, b: api([a, b]))


def _combinations_ref(x):
    import itertools

    return np.array(list(itertools.combinations(x, 2)), x.dtype)


_S("combinations", _combinations_ref, [((4,), "any")], kwargs={"r": 2})
_S("add_n", lambda a, b: a + b, [(_SH, "any"), (_SH, "any")],
   wrap=lambda api: lambda a, b: api([a, b]))
_S("assign", lambda x: x.copy(), _U)
_S("clone", lambda x: x.copy(), _U)
_S("cast", lambda x: x.astype(np.float32), _U,
   kwargs={"dtype": "float32"}, dtypes=("float32",))


def _multiplex_ref(a, b, idx):
    stack = [a, b]
    return np.stack([stack[int(idx[i, 0])][i] for i in range(a.shape[0])])


_S("multiplex", _multiplex_ref,
   [(_SH, "any"), (_SH, "any"), ((3, 1), "idx2")],
   wrap=lambda api: lambda a, b, i: api([a, b], i), grad=False)

_S("einsum", lambda a, b: np.einsum("ij,jk->ik", a, b),
   [((3, 4), "any"), ((4, 2), "any")],
   wrap=lambda api: lambda a, b: api("ij,jk->ik", a, b))

# ---------------------------------------------------------------------------
# math extras
# ---------------------------------------------------------------------------
_S("bincount", lambda x, w: np.bincount(x, w, minlength=4),
   [((8,), "idx3"), ((8,), "any")], kwargs={"minlength": 4},
   grad_inputs=[1])
_S("bitwise_invert", np.invert, [(_SH, "int")], dtypes=("int32", "int64"),
   grad=False)
_S("vander", lambda x: np.vander(x, 3, increasing=True), [((4,), "any")],
   kwargs={"n": 3, "increasing": True})
_S("frexp", lambda x: np.frexp(x), [((4,), "pow2safe")], grad=False,
   dtypes=("float32",))
_S("sgn", np.sign, [(_SH, "nonzero")])
_S("isneginf", lambda x: np.isneginf(x), _U, grad=False)
_S("isposinf", lambda x: np.isposinf(x), _U, grad=False)
_S("isreal", lambda x: np.isreal(x), _U, grad=False)
_S("quantile", lambda x: np.quantile(x, 0.3, axis=1), [((3, 5), "distinct")],
   kwargs={"q": 0.3, "axis": 1}, dtypes=("float32",))
_S("nanquantile", lambda x: np.nanquantile(x, 0.3, axis=1),
   [((3, 5), "distinct")], kwargs={"q": 0.3, "axis": 1}, dtypes=("float32",))


def _renorm_ref(x):
    out = x.copy()
    for i in range(x.shape[0]):
        n = np.linalg.norm(x[i].ravel())
        if n > 1.0:
            out[i] = x[i] / n
    return out


_S("renorm", _renorm_ref, [(_SH, "any")],
   kwargs={"p": 2.0, "axis": 0, "max_norm": 1.0})


def _polar_pair(api):
    def f(a, b):
        import paddle_tpu as paddle

        c = api(a, b)
        return paddle.real(c), paddle.imag(c)

    return f


_S("polar", lambda a, t: (a * np.cos(t), a * np.sin(t)),
   [(_SH, "pos"), (_SH, "any")], wrap=_polar_pair, dtypes=("float32",))
_S("complex", lambda re, im: (re, im), [(_SH, "any"), (_SH, "any")],
   wrap=_polar_pair, dtypes=("float32",))


def _as_complex_wrap(api):
    def f(x):
        import paddle_tpu as paddle

        c = api(x)
        return paddle.real(c), paddle.imag(c)

    return f


_S("as_complex", lambda x: (x[..., 0], x[..., 1]), [((3, 2), "any")],
   wrap=_as_complex_wrap, dtypes=("float32",))


def _as_real_wrap(api):
    def f(x):
        import paddle_tpu as paddle

        return api(paddle.as_complex(x))

    return f


_S("as_real", lambda x: x, [((3, 2), "any")], wrap=_as_real_wrap,
   dtypes=("float32",))
_S("real", lambda x: x, _U)
_S("imag", lambda x: np.zeros_like(x), _U, grad=False)
_S("conj", lambda x: x, _U)
_S("angle", lambda x: np.angle(x), [(_SH, "nonzero")], grad=False)
_S("floor_divide", np.floor_divide, [(_SH, "offint"), (_SH, "nonzero")],
   grad=False)
_S("gammainc", lambda x, y: sp.gammainc(x, y), [(_SH, "pos"), (_SH, "pos")],
   grad=False, tol={"float16": (3e-2, 3e-2), "bfloat16": (8e-2, 8e-2)})
_S("gammaincc", lambda x, y: sp.gammaincc(x, y), [(_SH, "pos"), (_SH, "pos")],
   grad=False, tol={"float16": (3e-2, 3e-2), "bfloat16": (8e-2, 8e-2)})


def _pdist_ref(x):
    n = x.shape[0]
    out = []
    for i in range(n):
        for j in range(i + 1, n):
            out.append(np.linalg.norm(x[i] - x[j]))
    return np.array(out, x.dtype)


_S("pdist", _pdist_ref, [((4, 3), "any")])

# ---------------------------------------------------------------------------
# long-tail: fill/diag, segment/graph, signal-windowing, decode ops
# ---------------------------------------------------------------------------


def _fill_diag_ref(x):
    out = x.copy()
    np.fill_diagonal(out, 0.3)
    return out


_S("fill_diagonal_", _fill_diag_ref, _U, kwargs={"value": 0.3},
   wrap=lambda api: lambda x, **kw: api(x.clone(), **kw))


def _fill_diag_tensor_ref(x, y):
    out = x.copy()
    for i in range(min(x.shape)):
        out[i, i] = y[i]
    return out


_S("fill_diagonal_tensor", _fill_diag_tensor_ref,
   [(_SH, "any"), ((3,), "any")])

_S("reduce_as", lambda x, t: x.sum(0, keepdims=True),
   [(_SH, "any"), ((1, 4), "any")], grad_inputs=[0])


def _clip_by_norm_ref(x):
    n = np.linalg.norm(x.ravel())
    return x * (1.0 / n) if n > 1.0 else x


_S("clip_by_norm", _clip_by_norm_ref, _U, kwargs={"max_norm": 1.0})


def _segment_ref(reducer):
    def f(x, seg):
        k = int(seg.max()) + 1
        return np.stack([reducer(x[seg == i]) for i in range(k)])

    return f


_S("segment_sum", _segment_ref(lambda v: v.sum(0)),
   [((6, 3), "any"), ((6,), "segsorted")], grad_inputs=[0])
_S("segment_mean", _segment_ref(lambda v: v.mean(0)),
   [((6, 3), "any"), ((6,), "segsorted")], grad_inputs=[0])
_S("segment_max", _segment_ref(lambda v: v.max(0)),
   [((6, 3), "distinct"), ((6,), "segsorted")], grad_inputs=[0])
_S("segment_min", _segment_ref(lambda v: v.min(0)),
   [((6, 3), "distinct"), ((6,), "segsorted")], grad_inputs=[0])


def _send_u_recv_ref(x, src, dst):
    out = np.zeros_like(x)
    np.add.at(out, dst, x[src])
    return out


_S("send_u_recv", _send_u_recv_ref,
   [((3, 4), "any"), ((5,), "idx3"), ((5,), "idx3")],
   kwargs={"reduce_op": "SUM"}, grad_inputs=[0])


def _shard_index_ref(x):
    # index_num=6, nshards=2, shard_id=0 -> shard size 3
    out = np.where((x >= 0) & (x < 3), x, -1)
    return out


_S("shard_index", _shard_index_ref, [((4, 1), "idx3")],
   kwargs={"index_num": 6, "nshards": 2, "shard_id": 0},
   dtypes=("int32", "int64"), grad=False)


def _frame_ref(x):
    # frame_length=4, hop_length=2, axis=-1 on length-8 signal -> 3 frames
    return np.stack([x[..., i * 2:i * 2 + 4] for i in range(3)], axis=-1)


_S("frame", _frame_ref, [((2, 8), "any")],
   kwargs={"frame_length": 4, "hop_length": 2})


def _overlap_add_ref(x):
    # frames [..., frame_length=4, n=3], hop=2 -> length 4 + 2*2 = 8
    out = np.zeros(x.shape[:-2] + (8,), x.dtype)
    for i in range(x.shape[-1]):
        out[..., i * 2:i * 2 + 4] += x[..., i]
    return out


_S("overlap_add", _overlap_add_ref, [((2, 4, 3), "any")],
   kwargs={"hop_length": 2})


def _gather_tree_ref(ids, parents):
    T, B, W = ids.shape
    out = np.empty_like(ids)
    for b in range(B):
        for w in range(W):
            cur = w
            for t in range(T - 1, -1, -1):
                out[t, b, w] = ids[t, b, cur]
                cur = parents[t, b, cur]
    return out


_S("gather_tree", _gather_tree_ref,
   [((4, 2, 3), "idx3"), ((4, 2, 3), "idx3")],
   dtypes=("int32", "int64"), grad=False)


def _viterbi_ref(pot, trans, lens):
    import itertools

    B, T, K = pot.shape
    scores = np.zeros((B,), pot.dtype)
    paths = np.zeros((B, T), np.int64)
    for b in range(B):
        best, arg = -np.inf, None
        for path in itertools.product(range(K), repeat=T):
            s = pot[b, 0, path[0]]
            for t in range(1, T):
                s += trans[path[t - 1], path[t]] + pot[b, t, path[t]]
            if s > best:
                best, arg = s, path
        scores[b] = best
        paths[b] = np.array(arg, np.int64)
    return scores, paths


_S("viterbi_decode",
   lambda pot, trans: _viterbi_ref(pot, trans, None),
   [((2, 4, 3), "distinct"), ((3, 3), "distinct")],
   kwargs={"include_bos_eos_tag": False}, grad=False, dtypes=("float32",))

# ---------------------------------------------------------------------------
# distribution host ops (log_prob/entropy dispatch names): the schema calls
# the distribution METHOD; oracle is the closed form
# (reference python/paddle/distribution/*.py)
# ---------------------------------------------------------------------------


def _dist_method(method, n_params):
    def wrap(cls):
        def f(*args):
            params, rest = args[:n_params], args[n_params:]
            d = cls(*params)
            return getattr(d, method)(*rest)

        return f

    return wrap


_S("normal_log_prob",
   lambda loc, sc, v: -((v - loc) ** 2) / (2 * sc ** 2)
   - np.log(sc) - 0.5 * np.log(2 * np.pi),
   [(_SH, "small"), (_SH, "pos"), (_SH, "any")],
   api="distribution.Normal", wrap=_dist_method("log_prob", 2))
_S("normal_entropy",
   lambda loc, sc: 0.5 + 0.5 * np.log(2 * np.pi) + np.log(sc),
   [(_SH, "small"), (_SH, "pos")], grad_inputs=[1],
   api="distribution.Normal", wrap=_dist_method("entropy", 2))
_S("bernoulli_log_prob",
   lambda p, v: v * np.log(p) + (1 - v) * np.log(1 - p),
   [(_SH, "prob"), (_SH, "binary")],
   api="distribution.Bernoulli", wrap=_dist_method("log_prob", 1),
   grad_inputs=[0],
   tol={"float16": (3e-2, 3e-2), "bfloat16": (8e-2, 8e-2)})
_S("bernoulli_entropy",
   lambda p: -(p * np.log(p) + (1 - p) * np.log1p(-p)),
   [(_SH, "prob")],
   api="distribution.Bernoulli", wrap=_dist_method("entropy", 1))


def _cat_log_prob_ref(logits, v):
    z = logits - sp.logsumexp(logits, axis=-1, keepdims=True)
    return np.take_along_axis(z, v[..., None].astype(np.int64),
                              -1)[..., 0]


_S("categorical_log_prob", _cat_log_prob_ref,
   [((3, 4), "any"), ((3,), "idx3")],
   api="distribution.Categorical", wrap=_dist_method("log_prob", 1))


def _cat_entropy_ref(logits):
    z = logits - sp.logsumexp(logits, axis=-1, keepdims=True)
    p = np.exp(z)
    return -(p * z).sum(-1)


_S("categorical_entropy", _cat_entropy_ref, [((3, 4), "any")],
   api="distribution.Categorical", wrap=_dist_method("entropy", 1))

# ---------------------------------------------------------------------------
# fft family (dynamic dispatch site fft.py — names enumerated in
# DYNAMIC_DISPATCH; oracles np.fft / scipy.fft). Complex outputs compare
# as (real, imag) pairs; complex inputs are built from a real pair.
# ---------------------------------------------------------------------------


def _c2pair(api, *, cplx_in=False, axes_kw=None):
    def f(x, **kw):
        import paddle_tpu as paddle

        xin = paddle.as_complex(x) if cplx_in else x
        out = api(xin, **kw)
        if paddle.is_complex(out):
            return paddle.real(out), paddle.imag(out)
        return out

    return f


def _np_pair(res):
    if np.iscomplexobj(res):
        return (np.real(res).astype(np.float32),
                np.imag(res).astype(np.float32))
    return res.astype(np.float32)


_FT_TOL = {"float16": (3e-2, 3e-2), "bfloat16": (1e-1, 1e-1)}

_S("fft", lambda x: _np_pair(np.fft.fft(x)), [((8,), "any")],
   api="fft.fft", wrap=_c2pair, tol=_FT_TOL, dtypes=("float32",))
_S("ifft", lambda x: _np_pair(np.fft.ifft(x[..., 0] + 1j * x[..., 1])),
   [((8, 2), "any")], api="fft.ifft",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("rfft", lambda x: _np_pair(np.fft.rfft(x)), [((8,), "any")],
   api="fft.rfft", wrap=_c2pair, dtypes=("float32",))
_S("irfft", lambda x: np.fft.irfft(x[..., 0] + 1j * x[..., 1]).astype(np.float32),
   [((5, 2), "any")], api="fft.irfft",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("hfft", lambda x: np.fft.hfft(x[..., 0] + 1j * x[..., 1]).astype(np.float32),
   [((5, 2), "any")], api="fft.hfft",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("ihfft", lambda x: _np_pair(np.fft.ihfft(x)), [((8,), "any")],
   api="fft.ihfft", wrap=_c2pair, dtypes=("float32",))
_S("fft2", lambda x: _np_pair(np.fft.fft2(x)), [((4, 4), "any")],
   api="fft.fft2", wrap=_c2pair, dtypes=("float32",))
_S("ifft2", lambda x: _np_pair(np.fft.ifft2(x[..., 0] + 1j * x[..., 1])),
   [((4, 4, 2), "any")], api="fft.ifft2",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("rfft2", lambda x: _np_pair(np.fft.rfft2(x)), [((4, 4), "any")],
   api="fft.rfft2", wrap=_c2pair, dtypes=("float32",))
_S("irfft2", lambda x: np.fft.irfft2(x[..., 0] + 1j * x[..., 1]).astype(np.float32),
   [((4, 3, 2), "any")], api="fft.irfft2",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("fftn", lambda x: _np_pair(np.fft.fftn(x)), [((2, 3, 4), "any")],
   api="fft.fftn", wrap=_c2pair, dtypes=("float32",))
_S("ifftn", lambda x: _np_pair(np.fft.ifftn(x[..., 0] + 1j * x[..., 1])),
   [((2, 3, 4, 2), "any")], api="fft.ifftn",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("rfftn", lambda x: _np_pair(np.fft.rfftn(x)), [((2, 3, 4), "any")],
   api="fft.rfftn", wrap=_c2pair, dtypes=("float32",))
_S("irfftn", lambda x: np.fft.irfftn(x[..., 0] + 1j * x[..., 1]).astype(np.float32),
   [((2, 3, 3, 2), "any")], api="fft.irfftn",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("hfftn", lambda x: __import__("scipy.fft", fromlist=["hfftn"])
   .hfftn(x[..., 0] + 1j * x[..., 1]).astype(np.float32),
   [((3, 3, 2), "any")], api="fft.hfftn",
   wrap=lambda api: _c2pair(api, cplx_in=True), dtypes=("float32",))
_S("ihfftn", lambda x: _np_pair(np.asarray(
    __import__("scipy.fft", fromlist=["ihfftn"]).ihfftn(x))),
   [((4, 4), "any")], api="fft.ihfftn", wrap=_c2pair, dtypes=("float32",),
   grad=False)
_S("fftshift", lambda x: np.fft.fftshift(x), _U, api="fft.fftshift")
_S("ifftshift", lambda x: np.fft.ifftshift(x), _U, api="fft.ifftshift")

WHITE_LIST.update({
    "fftn": {"grad": "fp32 FD noise (~2e-3) over the 3-D transform's O(n) "
             "accumulation exceeds tolerance; 1-D/2-D variants cover the "
             "same vjp path"},
    "rfftn": {"grad": "same FD-noise mechanism as fftn"},
})

# ---------------------------------------------------------------------------
# nn functional: conv / pool / norm / loss families. Oracle = torch CPU
# (the reference validates these against cuDNN; test_torch_oracle.py
# established torch-CPU as the independent oracle — here the same oracle
# runs under the dtype-sweep/FD-grad harness).
# ---------------------------------------------------------------------------
_NN_TOL = {"float16": (3e-2, 3e-2), "bfloat16": (8e-2, 8e-2)}


def _torch_ref(fn_name, *, module="nn.functional", post=None, **tkw):
    def ref(*arrays):
        import torch as _torch

        mod = _torch
        for part in module.split("."):
            mod = getattr(mod, part)
        res = getattr(mod, fn_name)(*[_t(a) for a in arrays], **tkw)
        res = _tn(res)
        return post(res) if post is not None else res

    return ref


# FD noise bound for many-term fp32 accumulations: the FD quotient is
# computed from an fp32 scalarized total T, so its granularity is
# ~eps_f32*|T|/(2*1e-3) ≈ 1e-2 for |T|~30 — an honest limit of fp32
# central differences, not analytic-gradient error (the analytic side is
# the jax vjp, exact to fp32)
_GRAD_TOL_ACC = (2e-2, 5e-2)

_S("conv2d", _torch_ref("conv2d", stride=1, padding=1),
   [((2, 3, 5, 5), "any"), ((4, 3, 3, 3), "any"), ((4,), "any")],
   api="nn.functional.conv2d", kwargs={"stride": 1, "padding": 1},
   tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)
_S("conv1d", _torch_ref("conv1d", stride=2, padding=1),
   [((2, 3, 8), "any"), ((4, 3, 3), "any"), ((4,), "any")],
   api="nn.functional.conv1d", kwargs={"stride": 2, "padding": 1},
   tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)
_S("conv3d", _torch_ref("conv3d", stride=1, padding=0),
   [((1, 2, 4, 4, 4), "any"), ((3, 2, 2, 2, 2), "any"), ((3,), "any")],
   api="nn.functional.conv3d", kwargs={"stride": 1, "padding": 0},
   tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)
_S("conv2d_transpose", _torch_ref("conv_transpose2d", stride=2, padding=1),
   [((1, 3, 4, 4), "any"), ((3, 2, 3, 3), "any"), ((2,), "any")],
   api="nn.functional.conv2d_transpose",
   kwargs={"stride": 2, "padding": 1}, tol=_NN_TOL,
   grad_tol=_GRAD_TOL_ACC)
_S("max_pool2d", _torch_ref("max_pool2d", kernel_size=2, stride=2),
   [((2, 2, 4, 4), "distinct")],
   api="nn.functional.max_pool2d", kwargs={"kernel_size": 2, "stride": 2})
_S("avg_pool2d", _torch_ref("avg_pool2d", kernel_size=2, stride=2),
   [((2, 2, 4, 4), "any")],
   api="nn.functional.avg_pool2d", kwargs={"kernel_size": 2, "stride": 2})
_S("max_pool1d", _torch_ref("max_pool1d", kernel_size=2, stride=2),
   [((2, 2, 8), "distinct")],
   api="nn.functional.max_pool1d", kwargs={"kernel_size": 2, "stride": 2})
_S("avg_pool1d", _torch_ref("avg_pool1d", kernel_size=2, stride=2),
   [((2, 2, 8), "any")],
   api="nn.functional.avg_pool1d", kwargs={"kernel_size": 2, "stride": 2})
_S("adaptive_avg_pool2d", _torch_ref("adaptive_avg_pool2d", output_size=2),
   [((2, 2, 4, 6), "any")],
   api="nn.functional.adaptive_avg_pool2d", kwargs={"output_size": 2})
_S("adaptive_max_pool2d", _torch_ref("adaptive_max_pool2d", output_size=2),
   [((2, 2, 4, 6), "distinct")],
   api="nn.functional.adaptive_max_pool2d", kwargs={"output_size": 2})
_S("lp_pool2d", _torch_ref("lp_pool2d", norm_type=2.0, kernel_size=2),
   [((2, 2, 4, 4), "pos")],
   api="nn.functional.lp_pool2d",
   kwargs={"norm_type": 2.0, "kernel_size": 2}, tol=_NN_TOL)


def _max_pool2d_mask_ref(x):
    import torch as _torch

    out, idx = _torch.nn.functional.max_pool2d(
        _t(x), kernel_size=2, stride=2, return_indices=True)
    return _tn(out), _tn(idx)


_S("max_pool2d_with_mask", _max_pool2d_mask_ref, [((2, 2, 4, 4), "distinct")],
   api="nn.functional.max_pool2d",
   kwargs={"kernel_size": 2, "stride": 2, "return_mask": True},
   grad=False, dtypes=("float32",))


def _max_unpool2d_ref(x, idx):
    out = np.zeros((1, 1, 16), x.dtype)
    flat_x = x.reshape(1, 1, -1)
    flat_i = idx.reshape(1, 1, -1)
    for j in range(flat_x.shape[-1]):
        out[0, 0, flat_i[0, 0, j]] = flat_x[0, 0, j]
    return out.reshape(1, 1, 4, 4)


_S("max_unpool2d", _max_unpool2d_ref,
   [((1, 1, 2, 2), "any"), ((1, 1, 2, 2), "dperm16")],
   api="nn.functional.max_unpool2d", kwargs={"kernel_size": 2},
   grad_inputs=[0], dtypes=("float32",))

def _layer_norm_ref(x, w, b):
    import torch as _torch

    return _tn(_torch.nn.functional.layer_norm(_t(x), [4], _t(w), _t(b)))


_S("layer_norm", _layer_norm_ref,
   [((3, 4), "any"), ((4,), "pos"), ((4,), "any")],
   api="nn.functional.layer_norm", kwargs={"normalized_shape": [4]},
   wrap=lambda api: lambda x, w, b, normalized_shape: api(
       x, normalized_shape, w, b),
   tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)


def _rms_norm_ref(x, w):
    return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w


_S("rms_norm", _rms_norm_ref, [((3, 4), "any"), ((4,), "pos")],
   api="nn.functional.rms_norm", tol=_NN_TOL)


def _batch_norm_ref(x, rm, rv, w, b):
    return ((x - rm[:, None, None]) / np.sqrt(rv[:, None, None] + 1e-5)
            * w[:, None, None] + b[:, None, None])


_S("batch_norm", _batch_norm_ref,
   [((2, 3, 2, 2), "any"), ((3,), "small"), ((3,), "pos"), ((3,), "pos"),
    ((3,), "any")],
   api="nn.functional.batch_norm", kwargs={"training": False},
   grad_inputs=[0, 3, 4], tol=_NN_TOL)
def _group_norm_ref(x, w, b):
    import torch as _torch

    return _tn(_torch.nn.functional.group_norm(_t(x), 2, _t(w), _t(b)))


_S("group_norm", _group_norm_ref,
   [((2, 4, 3, 3), "any"), ((4,), "pos"), ((4,), "any")],
   api="nn.functional.group_norm", kwargs={"num_groups": 2},
   wrap=lambda api: lambda x, w, b, num_groups: api(x, num_groups, w, b),
   tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)
_S("instance_norm", _torch_ref("instance_norm"),
   [((2, 3, 4, 4), "any")],
   api="nn.functional.instance_norm", tol=_NN_TOL)
_S("local_response_norm", _torch_ref("local_response_norm", size=3),
   [((2, 4, 3, 3), "any")],
   api="nn.functional.local_response_norm", kwargs={"size": 3},
   tol=_NN_TOL)


def _spectral_norm_ref(w):
    wm = w.reshape(w.shape[0], -1).astype(np.float64)
    v = np.ones((wm.shape[1],)) / np.sqrt(wm.shape[1])
    u = wm @ v
    u /= max(np.linalg.norm(u), 1e-12)
    v = wm.T @ u
    v /= max(np.linalg.norm(v), 1e-12)
    sigma = np.linalg.norm(wm @ v)
    return (w / max(sigma, 1e-12)).astype(w.dtype)


_S("spectral_norm", _spectral_norm_ref, [((3, 4), "any")],
   api="nn.functional.spectral_norm_value", tol=_NN_TOL)

_S("linear", lambda x, w, b: x @ w + b,
   [((3, 4), "any"), ((4, 5), "any"), ((5,), "any")],
   api="nn.functional.linear", tol=_NN_TOL)
_S("bilinear", lambda x1, x2, w, b: np.einsum("oij,bi,bj->bo", w, x1, x2) + b,
   [((3, 4), "any"), ((3, 5), "any"), ((2, 4, 5), "any"), ((1, 2), "any")],
   api="nn.functional.bilinear", tol=_NN_TOL)
_S("embedding", lambda ids, w: w[ids],
   [((3, 2), "idx3"), ((5, 4), "any")],
   api="nn.functional.embedding", grad_inputs=[1])
_S("embedding_bag", lambda ids, w: w[ids].mean(1),
   [((3, 2), "idx3"), ((5, 4), "any")],
   api="nn.functional.embedding_bag", kwargs={"mode": "mean"},
   grad_inputs=[1],
   wrap=lambda api: lambda ids, w, **kw: api(ids, w, **kw))
_S("prelu", lambda x, w: np.where(x > 0, x, w[None, :, None, None] * x),
   [((2, 3, 2, 2), "any"), ((3,), "prob")],
   api="nn.functional.prelu")


def _maxout_ref(x):
    n, c, h, w = x.shape
    return x.reshape(n, c // 2, 2, h, w).max(2)


_S("maxout", _maxout_ref, [((2, 4, 3, 3), "distinct")],
   api="nn.functional.maxout", kwargs={"groups": 2})
_S("glu", _torch_ref("glu"), [((3, 4), "any")], api="nn.functional.glu")
_S("interpolate", _torch_ref("interpolate", size=[5, 5], mode="bilinear",
                             align_corners=False),
   [((1, 2, 3, 3), "any")],
   api="nn.functional.interpolate",
   kwargs={"size": [5, 5], "mode": "bilinear", "align_corners": False},
   tol=_NN_TOL)
_S("grid_sample", _torch_ref("grid_sample", mode="bilinear",
                             padding_mode="zeros", align_corners=True),
   [((1, 2, 3, 3), "any"), ((1, 4, 4, 2), "unit")],
   api="nn.functional.grid_sample", kwargs={"align_corners": True},
   tol=_NN_TOL)
_S("affine_grid", lambda th: _torch_ref("affine_grid", size=[2, 2, 3, 3],
                                        align_corners=True)(th),
   [((2, 2, 3), "any")],
   api="nn.functional.affine_grid",
   kwargs={"out_shape": [2, 2, 3, 3], "align_corners": True})
_S("fold", _torch_ref("fold", output_size=[4, 4], kernel_size=2, stride=2),
   [((1, 8, 4), "any")],
   api="nn.functional.fold",
   kwargs={"output_sizes": [4, 4], "kernel_sizes": 2, "strides": 2})
_S("unfold", _torch_ref("unfold", kernel_size=2, stride=2),
   [((1, 2, 4, 4), "any")],
   api="nn.functional.unfold",
   kwargs={"kernel_sizes": 2, "strides": 2})


def _pixel_shuffle_ref(x):
    import torch as _torch

    return _tn(_torch.nn.functional.pixel_shuffle(_t(x), 2))


_S("pixel_shuffle", _pixel_shuffle_ref, [((1, 4, 2, 2), "any")],
   api="nn.functional.pixel_shuffle", kwargs={"upscale_factor": 2})


def _pixel_unshuffle_ref(x):
    import torch as _torch

    return _tn(_torch.nn.functional.pixel_unshuffle(_t(x), 2))


_S("pixel_unshuffle", _pixel_unshuffle_ref, [((1, 1, 4, 4), "any")],
   api="nn.functional.pixel_unshuffle", kwargs={"downscale_factor": 2})


def _channel_shuffle_ref(x):
    n, c, h, w = x.shape
    return x.reshape(n, 2, c // 2, h, w).swapaxes(1, 2).reshape(n, c, h, w)


_S("channel_shuffle", _channel_shuffle_ref, [((1, 4, 2, 2), "any")],
   api="nn.functional.channel_shuffle", kwargs={"groups": 2})


def _temporal_shift_ref(x):
    nt, c, h, w = x.shape
    a = x.reshape(nt // 2, 2, c, h, w)
    fold = c // 4
    out = np.zeros_like(a)
    out[:, :-1, :fold] = a[:, 1:, :fold]
    out[:, 1:, fold:2 * fold] = a[:, :-1, fold:2 * fold]
    out[:, :, 2 * fold:] = a[:, :, 2 * fold:]
    return out.reshape(nt, c, h, w)


_S("temporal_shift", _temporal_shift_ref, [((4, 4, 2, 2), "any")],
   api="nn.functional.temporal_shift", kwargs={"seg_num": 2})


def _sequence_mask_ref(lens):
    return (np.arange(4)[None, :] < lens[:, None]).astype(np.int64)


_S("sequence_mask", _sequence_mask_ref, [((3,), "posint")],
   api="nn.functional.sequence_mask", kwargs={"maxlen": 4},
   dtypes=("int32",), grad=False,
   wrap=lambda api: lambda lens, **kw: api(lens.astype("int32"), **kw))


def _sdpa_ref(q, k, v):
    import torch as _torch

    o = _torch.nn.functional.scaled_dot_product_attention(
        _t(q).transpose(1, 2), _t(k).transpose(1, 2), _t(v).transpose(1, 2))
    return _tn(o.transpose(1, 2))


_S("sdpa", _sdpa_ref,
   [((2, 4, 2, 4), "any"), ((2, 4, 2, 4), "any"), ((2, 4, 2, 4), "any")],
   api="nn.functional.scaled_dot_product_attention", tol=_NN_TOL,
   grad_tol=_GRAD_TOL_ACC)

# ---- losses ----
_S("bce_with_logits", _torch_ref("binary_cross_entropy_with_logits"),
   [((3, 4), "any"), ((3, 4), "binary")],
   api="nn.functional.binary_cross_entropy_with_logits", grad_inputs=[0],
   tol=_NN_TOL)
_S("cross_entropy",
   lambda x, lab: _torch_ref("cross_entropy")(x, lab.astype(np.int64)),
   [((4, 3), "any"), ((4,), "idx3")],
   api="nn.functional.cross_entropy", tol=_NN_TOL)
_S("nll_loss",
   lambda x, lab: _torch_ref("nll_loss")(
       np.log(np.exp(x) / np.exp(x).sum(-1, keepdims=True)),
       lab.astype(np.int64)),
   [((4, 3), "any"), ((4,), "idx3")],
   api="nn.functional.nll_loss",
   wrap=lambda api: lambda x, lab: api(
       __import__("paddle_tpu").nn.functional.log_softmax(x, -1), lab),
   tol=_NN_TOL)
_S("huber_loss", _torch_ref("huber_loss", delta=1.0),
   [((3, 4), "any"), ((3, 4), "any")],
   api="nn.functional.huber_loss", tol=_NN_TOL)
_S("square_error_cost", lambda x, y: (x - y) ** 2,
   [((3, 4), "any"), ((3, 4), "any")],
   api="nn.functional.square_error_cost")
_S("soft_margin_loss", _torch_ref("soft_margin_loss"),
   [((3, 4), "any"), ((3, 4), "pm1")],
   api="nn.functional.soft_margin_loss", grad_inputs=[0], tol=_NN_TOL)
_S("hinge_embedding_loss", _torch_ref("hinge_embedding_loss"),
   [((3, 4), "any"), ((3, 4), "pm1")],
   api="nn.functional.hinge_embedding_loss", grad_inputs=[0], tol=_NN_TOL)
_S("margin_ranking_loss", _torch_ref("margin_ranking_loss"),
   [((3, 4), "any"), ((3, 4), "any"), ((3, 4), "pm1")],
   api="nn.functional.margin_ranking_loss", grad_inputs=[0, 1],
   tol=_NN_TOL)
_S("multi_label_soft_margin_loss",
   _torch_ref("multilabel_soft_margin_loss"),
   [((3, 4), "any"), ((3, 4), "binary")],
   api="nn.functional.multi_label_soft_margin_loss", grad_inputs=[0],
   tol=_NN_TOL)
_S("triplet_margin_loss", _torch_ref("triplet_margin_loss"),
   [((3, 4), "any"), ((3, 4), "any"), ((3, 4), "any")],
   api="nn.functional.triplet_margin_loss", tol=_NN_TOL)
_S("poisson_nll_loss", _torch_ref("poisson_nll_loss"),
   [((3, 4), "small"), ((3, 4), "pos")],
   api="nn.functional.poisson_nll_loss", grad_inputs=[0], tol=_NN_TOL)
_S("pairwise_distance", _torch_ref("pairwise_distance"),
   [((3, 4), "any"), ((3, 4), "any")],
   api="nn.functional.pairwise_distance", tol=_NN_TOL)


def _dice_loss_ref(x, lab):
    lab_i = lab.astype(np.int64)
    one = np.eye(x.shape[-1])[lab_i.reshape(-1)].reshape(x.shape)
    inter = (x * one).sum(-1)
    union = x.sum(-1) + one.sum(-1)
    return (1 - (2 * inter + 1e-5) / (union + 1e-5)).mean()


_S("dice_loss", _dice_loss_ref, [((3, 4), "prob"), ((3, 1), "idx3")],
   api="nn.functional.dice_loss",
   wrap=lambda api: lambda x, lab: api(x, lab))
_S("log_loss",
   lambda p, y: -(y * np.log(p + 1e-4) + (1 - y) * np.log(1 - p + 1e-4)),
   [((3, 1), "prob"), ((3, 1), "binary")],
   api="nn.functional.log_loss", grad_inputs=[0])
_S("label_smooth",
   lambda lab: 0.9 * lab + 0.1 / lab.shape[-1],
   [((3, 4), "binary")],
   api="nn.functional.label_smooth", kwargs={"epsilon": 0.1}, grad=False)


def _ctc_ref(lp, lab):
    import torch as _torch

    T, B, C = lp.shape
    return _tn(_torch.nn.functional.ctc_loss(
        _t(lp), _t(lab.astype(np.int64)),
        _torch.full((B,), T, dtype=_torch.long),
        _torch.full((B,), lab.shape[1], dtype=_torch.long),
        blank=0, reduction="none", zero_infinity=False))


def _ctc_wrap(api):
    def f(lp, lab):
        import paddle_tpu as paddle

        T, B, C = lp.shape
        return api(lp, lab,
                   paddle.to_tensor(np.full((B,), T, np.int64)),
                   paddle.to_tensor(np.full((B,), lab.shape[1], np.int64)),
                   blank=0, reduction="none")

    return f


def _lsm(x):
    return x - sp.logsumexp(x, axis=-1, keepdims=True)


_S("ctc_loss", lambda lp, lab: _ctc_ref(_lsm(lp), 1 + lab),
   [((6, 2, 4), "any"), ((2, 2), "idx2")],
   api="nn.functional.ctc_loss",
   wrap=lambda api: _ctc_wrap(lambda lp, lab, *r, **kw: api(
       __import__("paddle_tpu").nn.functional.log_softmax(lp, -1),
       lab + 1, *r, **kw)),
   grad_inputs=[0], tol=_NN_TOL)


def _margin_ce_ref(cos, lab):
    lab_i = lab.reshape(-1).astype(np.int64)
    onehot = np.eye(cos.shape[-1])[lab_i]
    theta = np.arccos(np.clip(cos, -1 + 1e-7, 1 - 1e-7))
    target = np.cos(1.0 * theta + 0.5) - 0.0
    adjusted = np.where(onehot > 0, target, cos) * 64.0
    z = _lsm(adjusted)
    return (-(onehot * z).sum(-1, keepdims=True)).mean()


_S("margin_cross_entropy", _margin_ce_ref,
   [((3, 4), "unit"), ((3,), "idx3")],
   api="nn.functional.margin_cross_entropy",
   tol={"float16": (2e-1, 5e-2), "bfloat16": (5e-1, 1e-1)})

# ---------------------------------------------------------------------------
# linalg (reference ops.yaml cholesky_solve/eigh/qr/svd/lu/... family).
# Factorization outputs are compared in sign-canonical form (|Q|, |U|...):
# with distinct eigen/singular values the factors are unique up to column
# sign, which abs() quotients out.
# ---------------------------------------------------------------------------
# LAPACK-backed ops: XLA:CPU lowers them through lapack kernels that only
# support fp32/fp64, so the low-precision sweep stays out (on TPU these
# dispatch to different lowerings, exercised by the on-chip lane)
_S("inv", np.linalg.inv, [((3, 3), "wellcond")], api="linalg.inv",
   dtypes=("float32",), grad_tol=_GRAD_TOL_ACC)
_S("matrix_exp", lambda x: __import__("scipy.linalg", fromlist=["expm"])
   .expm(x), [((3, 3), "small")], api="linalg.matrix_exp",
   dtypes=("float32",), grad_tol=_GRAD_TOL_ACC)
_S("multi_dot", lambda a, b, c: a @ b @ c,
   [((2, 3), "any"), ((3, 4), "any"), ((4, 2), "any")],
   api="linalg.multi_dot", wrap=lambda api: lambda a, b, c: api([a, b, c]),
   tol=_NN_TOL)
_S("vector_norm", lambda x: np.linalg.norm(x.ravel(), 3.0),
   _U, api="linalg.vector_norm", kwargs={"p": 3.0})
_S("matrix_norm", lambda x: np.linalg.norm(x, "fro"),
   _U, api="linalg.matrix_norm", kwargs={"p": "fro"})
_S("cond", lambda x: np.linalg.cond(x), [((3, 3), "wellcond")],
   api="linalg.cond", grad=False, dtypes=("float32",))
_S("cov", lambda x: np.cov(x), [((3, 6), "any")], api="linalg.cov",
   grad_tol=_GRAD_TOL_ACC)
_S("corrcoef", lambda x: np.corrcoef(x), [((3, 6), "any")],
   api="linalg.corrcoef", grad_tol=_GRAD_TOL_ACC, tol=_NN_TOL)


def _spd(rng, sh):
    a = rng.uniform(-1.0, 1.0, sh).astype(np.float32)
    return a @ a.T + np.eye(sh[0], dtype=np.float32) * sh[0]


_DOMAINS["spd"] = _spd
# well-conditioned general square matrix: dominant diagonal
_DOMAINS["wellcond"] = lambda rng, sh: (
    rng.uniform(-1.0, 1.0, sh) + np.eye(sh[0]) * sh[0]).astype(np.float32)


def _chol_solve_ref(y, b):
    L = np.linalg.cholesky(y)
    return np.linalg.solve(L @ L.T, b)


def _chol_wrap(api):
    def f(y, b):
        import paddle_tpu as paddle

        return api(b, paddle.linalg.cholesky(y))

    return f


_S("cholesky_solve", _chol_solve_ref, [((3, 3), "spd"), ((3, 2), "any")],
   api="linalg.cholesky_solve", wrap=_chol_wrap, dtypes=("float32",),
   grad_tol=_GRAD_TOL_ACC)


def _chol_inv_wrap(api):
    def f(y):
        import paddle_tpu as paddle

        return api(paddle.linalg.cholesky(y))

    return f


_S("cholesky_inverse", lambda y: np.linalg.inv(y), [((3, 3), "spd")],
   api="linalg.cholesky_inverse", wrap=_chol_inv_wrap,
   dtypes=("float32",), grad_tol=_GRAD_TOL_ACC)

_S("eigh", lambda x: (np.linalg.eigh(x)[0], np.abs(np.linalg.eigh(x)[1])),
   [((3, 3), "spd")], api="linalg.eigh",
   wrap=lambda api: lambda x: (lambda wv: (wv[0], wv[1].abs()))(api(x)),
   grad=False, dtypes=("float32",))
_S("qr", lambda x: tuple(np.abs(m) for m in np.linalg.qr(x)),
   [((4, 3), "any")], api="linalg.qr",
   wrap=lambda api: lambda x: tuple(m.abs() for m in api(x)),
   grad=False, dtypes=("float32",))
_S("svd", lambda x: (np.abs(np.linalg.svd(x, full_matrices=False)[0]),
                     np.linalg.svd(x, full_matrices=False)[1],
                     np.abs(np.linalg.svd(x, full_matrices=False)[2])),
   [((4, 3), "any")], api="linalg.svd",
   wrap=lambda api: lambda x: tuple(m.abs() for m in api(x)),
   grad=False, dtypes=("float32",))


def _lu_ref(x):
    from scipy.linalg import lu_factor

    lu_mat, piv = lu_factor(x)
    return lu_mat.astype(np.float32), (piv + 1).astype(np.int32)


_S("lu", _lu_ref, [((3, 3), "wellcond")], api="linalg.lu",
   grad=False, dtypes=("float32",))


def _lu_unpack_ref(x):
    from scipy.linalg import lu

    P, L, U = lu(x)
    return P.astype(np.float32), L.astype(np.float32), U.astype(np.float32)


def _lu_unpack_wrap(api):
    def f(x):
        import paddle_tpu as paddle

        lu_mat, piv = paddle.linalg.lu(x)
        return api(lu_mat, piv)

    return f


_S("lu_unpack", _lu_unpack_ref, [((3, 3), "wellcond")],
   api="linalg.lu_unpack", wrap=_lu_unpack_wrap, grad=False,
   dtypes=("float32",))


def _lstsq_wrap(api):
    def f(x, y):
        return api(x, y)[0]  # solution tensor only

    return f


_S("lstsq", lambda x, y: np.linalg.lstsq(x, y, rcond=None)[0],
   [((4, 3), "any"), ((4, 2), "any")], api="linalg.lstsq",
   wrap=_lstsq_wrap, grad=False, dtypes=("float32",))


def _householder_ref(a, tau):
    m, n = a.shape
    Q = np.eye(m)
    for i in range(tau.shape[0]):
        v = np.where(np.arange(m) < i, 0.0, a[:, i]).copy()
        v[i] = 1.0
        Q = Q @ (np.eye(m) - tau[i] * np.outer(v, v))
    return Q[:, :n].astype(np.float32)


_S("householder_product", _householder_ref,
   [((4, 3), "any"), ((3,), "prob")], api="linalg.householder_product",
   grad=False, dtypes=("float32",))
# impl applies the REDUCED Q (m, n), so `other` is (n, k)
_S("ormqr", lambda a, tau, c: _householder_ref(a, tau) @ c,
   [((4, 3), "any"), ((3,), "prob"), ((3, 2), "any")],
   api="linalg.ormqr", grad=False, dtypes=("float32",))

# ---------------------------------------------------------------------------
# sparse ops: the schema samples DENSE arrays; the wrap builds the sparse
# operand (reference sparse_ops.yaml; sparse/__init__.py to_sparse_coo)
# ---------------------------------------------------------------------------


def _sparsify(x):
    import paddle_tpu as paddle

    return paddle.to_tensor(x.numpy()
                            if hasattr(x, "numpy") else x).to_sparse_coo(2)


_S("sparse_matmul", lambda x, y: x @ y,
   [((3, 4), "maskany"), ((4, 2), "any")], api="sparse.matmul",
   wrap=lambda api: lambda x, y: api(_sparsify(x), y), grad_inputs=[1],
   tol=_NN_TOL)
_S("sparse_mv", lambda x, v: x @ v,
   [((3, 4), "maskany"), ((4,), "any")], api="sparse.mv",
   wrap=lambda api: lambda x, v: api(_sparsify(x), v), grad_inputs=[1],
   tol=_NN_TOL)
_S("sparse_addmm", lambda inp, x, y: inp + x @ y,
   [((3, 2), "any"), ((3, 4), "maskany"), ((4, 2), "any")],
   api="sparse.addmm",
   wrap=lambda api: lambda i, x, y: api(i, _sparsify(x), y),
   grad_inputs=[0, 2], tol=_NN_TOL)


def _masked_matmul_ref(x, y, m):
    return (x @ y) * (m != 0)


def _masked_matmul_wrap(api):
    def f(x, y, m):
        return api(x, y, _sparsify(m)).to_dense()

    return f


_S("sparse_masked_matmul", _masked_matmul_ref,
   [((3, 4), "any"), ((4, 3), "any"), ((3, 3), "maskany")],
   api="sparse.masked_matmul", wrap=_masked_matmul_wrap,
   grad=False, tol=_NN_TOL)

# ~half the entries exactly zero (sparse patterns with nonzero structure)
_DOMAINS["maskany"] = lambda rng, sh: (
    rng.uniform(-2.0, 2.0, sh) * (rng.rand(*sh) > 0.5)).astype(np.float32)

# ---------------------------------------------------------------------------
# vision ops (reference ops.yaml box_coder/roi_align/yolo_box/nms...)
# ---------------------------------------------------------------------------
# xyxy boxes with x2>x1, y2>y1 inside a 16x16 image: (x1, y1) sampled
# low, (x2, y2) sampled high
_DOMAINS["boxes"] = lambda rng, sh: np.concatenate(
    [rng.uniform(0, 7, sh[:-1] + (2,)),
     rng.uniform(8, 15, sh[:-1] + (2,))], -1).astype(np.float32)


def _box_area_ref(b):
    return (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])


_S("box_area", _box_area_ref, [((4, 4), "boxes")],
   api="vision.ops.box_area")


def _box_iou_ref(a, b):
    out = np.zeros((a.shape[0], b.shape[0]), np.float32)
    for i in range(a.shape[0]):
        for j in range(b.shape[0]):
            ix1, iy1 = max(a[i, 0], b[j, 0]), max(a[i, 1], b[j, 1])
            ix2, iy2 = min(a[i, 2], b[j, 2]), min(a[i, 3], b[j, 3])
            iw, ih = max(ix2 - ix1, 0), max(iy2 - iy1, 0)
            inter = iw * ih
            ua = _box_area_ref(a)[i] + _box_area_ref(b)[j] - inter
            out[i, j] = inter / ua
    return out


_S("box_iou", _box_iou_ref, [((3, 4), "boxes"), ((4, 4), "boxes")],
   api="vision.ops.box_iou", grad=False)


def _box_clip_ref(b):
    # im_info rows (h=10, w=12, scale=1): clip to [0, w-1] x [0, h-1]
    out = b.reshape(1, -1, 4).copy()
    out[..., 0::2] = np.clip(out[..., 0::2], 0, 11)
    out[..., 1::2] = np.clip(out[..., 1::2], 0, 9)
    return out


def _box_clip_wrap(api):
    def f(b):
        import paddle_tpu as paddle

        im = paddle.to_tensor(np.array([[10.0, 12.0, 1.0]], np.float32))
        return api(b.reshape([1, -1, 4]), im)

    return f


_S("box_clip", _box_clip_ref, [((4, 4), "boxes")],
   api="vision.ops_detection.box_clip", wrap=_box_clip_wrap, grad=False,
   dtypes=("float32",))


def _nms_ref(boxes):
    # pure-IoU NMS, descending box order = input order (no scores)
    keep, sup = [], np.zeros(boxes.shape[0], bool)
    for i in range(boxes.shape[0]):
        if sup[i]:
            continue
        keep.append(i)
        for j in range(i + 1, boxes.shape[0]):
            if _box_iou_ref(boxes[i:i + 1], boxes[j:j + 1])[0, 0] > 0.3:
                sup[j] = True
    return np.array(keep, np.int64)


_S("nms", _nms_ref, [((5, 4), "boxes")], api="vision.ops.nms",
   kwargs={"iou_threshold": 0.3}, grad=False, dtypes=("float32",))


def _roi_align_ref(x, boxes):
    import math as _m

    N, C, H, W = x.shape
    out = np.zeros((boxes.shape[0], C, 2, 2), np.float32)

    def bilinear(img, y, xx):
        y = min(max(y, 0.0), H - 1.0)
        xx = min(max(xx, 0.0), W - 1.0)
        y0, x0 = int(_m.floor(y)), int(_m.floor(xx))
        y1, x1 = min(y0 + 1, H - 1), min(x0 + 1, W - 1)
        ly, lx = y - y0, xx - x0
        return (img[y0, x0] * (1 - ly) * (1 - lx) + img[y0, x1] * (1 - ly) * lx
                + img[y1, x0] * ly * (1 - lx) + img[y1, x1] * ly * lx)

    for r in range(boxes.shape[0]):
        x1, y1, x2, y2 = boxes[r]
        rw, rh = max(x2 - x1, 1e-3) / 2, max(y2 - y1, 1e-3) / 2
        for c in range(C):
            for ph in range(2):
                for pw in range(2):
                    # sampling_ratio=1: one sample at each bin center
                    sy = y1 + ph * rh + rh / 2
                    sx = x1 + pw * rw + rw / 2
                    out[r, c, ph, pw] = bilinear(x[0, c], sy, sx)
    return out


def _roi_wrap(api):
    def f(x, boxes, **kw):
        import paddle_tpu as paddle

        bn = paddle.to_tensor(np.array([boxes.shape[0]], np.int32))
        return api(x, boxes, bn, **kw)

    return f


_S("roi_align", _roi_align_ref,
   [((1, 2, 8, 8), "any"), ((3, 4), "boxes")],
   api="vision.ops.roi_align",
   kwargs={"output_size": 2, "sampling_ratio": 1, "aligned": False},
   wrap=_roi_wrap, grad_inputs=[0], tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)


def _roi_pool_ref(x, boxes):
    N, C, H, W = x.shape
    out = np.zeros((boxes.shape[0], C, 2, 2), np.float32)
    for r in range(boxes.shape[0]):
        x1, y1, x2, y2 = (int(round(v)) for v in boxes[r])
        rh, rw = max(y2 - y1 + 1, 1), max(x2 - x1 + 1, 1)
        for c in range(C):
            for ph in range(2):
                for pw in range(2):
                    hs = y1 + int(np.floor(ph * rh / 2.0))
                    he = y1 + int(np.ceil((ph + 1) * rh / 2.0))
                    ws = x1 + int(np.floor(pw * rw / 2.0))
                    we = x1 + int(np.ceil((pw + 1) * rw / 2.0))
                    hs, he = min(max(hs, 0), H), min(max(he, 0), H)
                    ws, we = min(max(ws, 0), W), min(max(we, 0), W)
                    patch = x[0, c, hs:he, ws:we]
                    out[r, c, ph, pw] = patch.max() if patch.size else 0.0
    return out


_S("roi_pool", _roi_pool_ref,
   [((1, 2, 8, 8), "distinct"), ((3, 4), "boxes")],
   api="vision.ops.roi_pool", kwargs={"output_size": 2},
   wrap=_roi_wrap, grad=False, dtypes=("float32",))

# ---------------------------------------------------------------------------
# incubate fused ops (reference fused_ops.yaml): semantics are pinned by
# plain-numpy references; the TPU win is XLA fusing them, not different math
# ---------------------------------------------------------------------------
_S("fused_rms_norm", _rms_norm_ref, [((3, 4), "any"), ((4,), "pos")],
   api="incubate.nn.functional.fused_rms_norm", tol=_NN_TOL)
_S("fused_layer_norm", _layer_norm_ref,
   [((3, 4), "any"), ((4,), "pos"), ((4,), "any")],
   api="incubate.nn.functional.fused_layer_norm", tol=_NN_TOL,
   grad_tol=_GRAD_TOL_ACC)
_S("swiglu", lambda x, y: x / (1 + np.exp(-x)) * y,
   [((3, 4), "any"), ((3, 4), "any")],
   api="incubate.nn.functional.swiglu", tol=_NN_TOL)
def _gelu_tanh(x):
    # jax.nn.gelu default approximate=True (tanh form)
    return 0.5 * x * (1 + np.tanh(np.sqrt(2 / np.pi)
                                  * (x + 0.044715 * x ** 3)))


_S("fused_bias_act",
   lambda x, b: _gelu_tanh(x + b),
   [((3, 4), "any"), ((4,), "any")],
   api="incubate.nn.functional.fused_bias_act",
   kwargs={"act_method": "gelu"}, tol=_NN_TOL)
_S("fused_linear", lambda x, w, b: x @ w + b,
   [((3, 4), "any"), ((4, 5), "any"), ((5,), "any")],
   api="incubate.nn.functional.fused_linear", tol=_NN_TOL)
_S("fused_linear_activation",
   lambda x, w, b: _gelu_tanh(x @ w + b),
   [((3, 4), "any"), ((4, 5), "any"), ((5,), "any")],
   api="incubate.nn.functional.fused_linear_activation", tol=_NN_TOL,
   grad_tol=_GRAD_TOL_ACC)


def _fused_ffn_ref(x, w1, w2, g2, b2):
    u = np.maximum(x @ w1, 0.0) @ w2 + x
    mu = u.mean(-1, keepdims=True)
    var = u.var(-1, keepdims=True)
    return (u - mu) / np.sqrt(var + 1e-5) * g2 + b2


_S("fused_feedforward", _fused_ffn_ref,
   [((3, 4), "any"), ((4, 8), "any"), ((8, 4), "any"), ((4,), "pos"),
    ((4,), "any")],
   api="incubate.nn.functional.fused_feedforward",
   wrap=lambda api: lambda x, w1, w2, g2, b2: api(
       x, w1, w2, ln2_scale=g2, ln2_bias=b2,
       dropout1_rate=0.0, dropout2_rate=0.0),
   tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)


def _rope_ref(q):
    B, S, H, D = q.shape
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    freqs = np.outer(np.arange(S), inv)
    c, s = np.cos(freqs)[None, :, None, :], np.sin(freqs)[None, :, None, :]
    half = D // 2
    x1, x2 = q[..., :half], q[..., half:]
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


_S("fused_rope", _rope_ref, [((2, 4, 2, 4), "any")],
   api="incubate.nn.functional.fused_rotary_position_embedding",
   wrap=lambda api: lambda q: api(q)[0], tol=_NN_TOL)

# ---------------------------------------------------------------------------
# eval-mode stochastic ops: deterministic branch under the sweep; the
# training=True random branch is white-listed (no fixed-seed oracle)
# ---------------------------------------------------------------------------
_S("dropout", lambda x: x, _U, api="nn.functional.dropout",
   kwargs={"training": False})
_S("alpha_dropout", lambda x: x, _U, api="nn.functional.alpha_dropout",
   kwargs={"training": False})
_S("feature_alpha_dropout", lambda x: x, _U,
   api="nn.functional.feature_alpha_dropout", kwargs={"training": False})
_S("rrelu",
   lambda x: np.where(x >= 0, x, ((1 / 8 + 1 / 3) / 2) * x), _U,
   api="nn.functional.rrelu", kwargs={"training": False})

# ---------------------------------------------------------------------------
# signal / audio
# ---------------------------------------------------------------------------


def _stft_ref(x):
    # n_fft=8, hop=4, window=ones, center=True reflect, onesided
    h = np.pad(x, [(0, 0), (4, 4)], mode="reflect")
    frames = np.stack([h[:, i * 4:i * 4 + 8] for i in range(5)], 1)
    spec = np.fft.rfft(frames, n=8, axis=-1)
    spec = np.swapaxes(spec, -1, -2)
    return (np.real(spec).astype(np.float32),
            np.imag(spec).astype(np.float32))


def _stft_wrap(api):
    def f(x):
        import paddle_tpu as paddle

        out = api(x, n_fft=8, hop_length=4)
        return paddle.real(out), paddle.imag(out)

    return f


_S("stft", _stft_ref, [((2, 16), "any")], api="signal.stft",
   wrap=_stft_wrap, dtypes=("float32",))


def _istft_ref(x):
    spec = x[..., 0] + 1j * x[..., 1]
    s = np.swapaxes(spec, -1, -2)          # [..., frames, freq]
    frames = np.fft.irfft(s, n=8, axis=-1)
    n_frames = frames.shape[-2]
    T = 8 + 4 * (n_frames - 1)
    out = np.zeros(frames.shape[:-2] + (T,))
    wsum = np.zeros(T)
    for i in range(n_frames):
        out[..., i * 4:i * 4 + 8] += frames[..., i, :]
        wsum[i * 4:i * 4 + 8] += 1.0
    out = out / np.where(wsum > 1e-11, wsum, 1.0)
    return out[..., 4:T - 4].astype(np.float32)


def _istft_wrap(api):
    def f(x):
        import paddle_tpu as paddle

        return api(paddle.as_complex(x).transpose([0, 2, 1]).transpose(
            [0, 2, 1]), n_fft=8, hop_length=4)

    return f


_S("istft", _istft_ref, [((2, 5, 5, 2), "any")], api="signal.istft",
   wrap=lambda api: lambda x: api(
       __import__("paddle_tpu").as_complex(x), n_fft=8, hop_length=4),
   dtypes=("float32",), grad_tol=_GRAD_TOL_ACC)


def _spectrogram_ref(x):
    from scipy.signal import get_window

    win = get_window("hann", 8, fftbins=True)
    h = np.pad(x, [(0, 0), (4, 4)], mode="reflect")
    frames = np.stack([h[:, i * 2:i * 2 + 8] for i in range(9)], 1)
    spec = np.fft.rfft(frames * win, n=8, axis=-1)
    return np.swapaxes(np.abs(spec) ** 2.0, -1, -2).astype(np.float32)


def _spectrogram_wrap(cls):
    def f(x):
        return cls(n_fft=8, hop_length=2, window="hann")(x)

    return f


_S("spectrogram", _spectrogram_ref, [((2, 16), "any")],
   api="audio.features.Spectrogram", wrap=_spectrogram_wrap,
   dtypes=("float32",), grad_tol=_GRAD_TOL_ACC)

# ---------------------------------------------------------------------------
# quantization / detection decode / tensor-unfold
# ---------------------------------------------------------------------------


def _fq_ref(x):
    q = np.clip(np.round(x / 2.0 * 127.0), -127, 127)
    return (q * 2.0 / 127.0).astype(np.float32)


_S("fake_quantize_dequantize", _fq_ref, [(_SH, "any")],
   api="quantization.quanters.fake_quant_dequant",
   kwargs={"scale": 2.0, "quant_bits": 8}, grad=False,
   dtypes=("float32",))

_S("unfold_tensor",
   lambda x: np.stack([x[..., i * 2:i * 2 + 4] for i in range(3)], -2),
   [((2, 8), "any")], api="unfold",
   kwargs={"axis": -1, "size": 4, "step": 2})


def _yolo_box_ref(feat, imgs):
    # na=1, anchors=(4,6), class_num=2, downsample=8, H=W=2, no clip
    sig = lambda v: 1 / (1 + np.exp(-v))
    N, C, H, W = feat.shape
    f = feat.reshape(N, 1, 7, H, W)
    gx, gy = np.meshgrid(np.arange(W), np.arange(H), indexing="xy")
    bx = (sig(f[:, :, 0]) + gx) / W
    by = (sig(f[:, :, 1]) + gy) / H
    bw = np.exp(f[:, :, 2]) * 4.0 / (W * 8)
    bh = np.exp(f[:, :, 3]) * 6.0 / (H * 8)
    conf = sig(f[:, :, 4])
    score = conf[:, :, None] * sig(f[:, :, 5:])
    imw = imgs[:, 1].astype(np.float32)[:, None, None, None]
    imh = imgs[:, 0].astype(np.float32)[:, None, None, None]
    boxes = np.stack([(bx - bw / 2) * imw, (by - bh / 2) * imh,
                      (bx + bw / 2) * imw, (by + bh / 2) * imh],
                     -1).reshape(N, H * W, 4)
    scores = np.moveaxis(score, 2, -1).reshape(N, H * W, 2)
    keep = (conf.reshape(N, H * W, 1) >= 0.01)
    return boxes * keep, scores * keep


def _yolo_box_wrap(api):
    def f(feat):
        import paddle_tpu as paddle

        imgs = paddle.to_tensor(np.array([[32, 32]], np.int32))
        return api(feat, imgs, anchors=[4, 6], class_num=2,
                   conf_thresh=0.01, downsample_ratio=8, clip_bbox=False)

    return f


_S("yolo_box", lambda feat: _yolo_box_ref(feat, np.array([[32, 32]])),
   [((1, 7, 2, 2), "any")], api="vision.ops_detection.yolo_box",
   wrap=_yolo_box_wrap, grad=False, dtypes=("float32",))


def _psroi_ref(x, boxes):
    # output_size=1: average each channel group over the box's cell span
    N, C, H, W = x.shape
    out = np.zeros((boxes.shape[0], C, 1, 1), np.float32)
    for r in range(boxes.shape[0]):
        x0, y0, x1, y1 = boxes[r]
        h = max(y1 - y0, 0.1)
        w = max(x1 - x0, 0.1)
        ys = np.arange(H)
        xs = np.arange(W)
        ym = (ys >= np.floor(y0)) & (ys < np.ceil(y0 + h))
        xm = (xs >= np.floor(x0)) & (xs < np.ceil(x0 + w))
        m = ym[:, None] & xm[None, :]
        cnt = max(m.sum(), 1)
        for c in range(C):
            out[r, c, 0, 0] = np.where(m, x[0, c], 0.0).sum() / cnt
    return out


_S("psroi_pool", _psroi_ref, [((1, 2, 8, 8), "any"), ((2, 4), "boxes")],
   api="vision.ops_detection.psroi_pool",
   kwargs={"output_size": 1},
   wrap=_roi_wrap, grad_inputs=[0], dtypes=("float32",),
   grad_tol=_GRAD_TOL_ACC)


def _box_coder_ref(prior, target):
    pw = prior[:, 2] - prior[:, 0]
    ph = prior[:, 3] - prior[:, 1]
    pcx = prior[:, 0] + pw * 0.5
    pcy = prior[:, 1] + ph * 0.5
    tw = target[:, 2] - target[:, 0]
    th = target[:, 3] - target[:, 1]
    tcx = target[:, 0] + tw * 0.5
    tcy = target[:, 1] + th * 0.5
    return np.stack([(tcx - pcx) / pw, (tcy - pcy) / ph,
                     np.log(tw / pw), np.log(th / ph)], 1)


def _box_coder_wrap(api):
    def f(prior, target):
        return api(prior, [1.0, 1.0, 1.0, 1.0], target,
                   code_type="encode_center_size")

    return f


_S("box_coder", _box_coder_ref, [((3, 4), "boxes"), ((3, 4), "boxes")],
   api="vision.ops.box_coder", wrap=_box_coder_wrap, grad=False,
   dtypes=("float32",))

def _fused_bias_dropout_residual_ln_ref(x, res, b, g, beta):
    h = res + x + b
    mu = h.mean(-1, keepdims=True)
    var = h.var(-1, keepdims=True)
    return (h - mu) / np.sqrt(var + 1e-5) * g + beta


_S("fused_bias_dropout_residual_ln", _fused_bias_dropout_residual_ln_ref,
   [(_SH, "any"), (_SH, "any"), ((4,), "any"), ((4,), "pos"), ((4,), "any")],
   api="incubate.nn.functional.fused_bias_dropout_residual_layer_norm",
   kwargs={"dropout_rate": 0.0}, dtypes=("float32",))

# ---------------------------------------------------------------------------
# weight-only quantization (nn/quant.py; reference
# python/paddle/nn/quant/quantized_linear.py)
# ---------------------------------------------------------------------------

_DOMAINS["int8w"] = lambda rng, sh: rng.randint(-127, 128, sh).astype(np.int8)


def _weight_quantize_ref(w):
    wt = w.astype(np.float32).T
    scale = np.abs(wt).max(axis=1) / 127.0
    q = np.clip(np.round(wt / np.maximum(scale, 1e-10)[:, None]),
                -127, 127).astype(np.int8)
    return q, scale


def _weight_dequantize_ref(q, s):
    return (q.astype(np.float32) * s[:, None]).T


def _weight_only_linear_ref(x, q, b, s):
    return x @ (q.astype(np.float32) * s[:, None]).T + b


_S("weight_quantize", _weight_quantize_ref, [((8, 6), "any")],
   api="nn.quant.weight_quantize", grad=False, dtypes=("float32",))
_S("weight_dequantize", _weight_dequantize_ref,
   [((6, 8), "int8w"), ((6,), "pos")], api="nn.quant.weight_dequantize",
   kwargs={"out_dtype": "float32"}, grad=False, dtypes=("float32",))
_S("weight_only_linear", _weight_only_linear_ref,
   [((2, 8), "any"), ((6, 8), "int8w"), ((6,), "any"), ((6,), "pos")],
   api="nn.quant.weight_only_linear", grad=False, dtypes=("float32",))

# ---------------------------------------------------------------------------
# Enforcement registries (tests/test_schema_enforcement.py).
#
# NO_SCHEMA_WHITE_LIST: ops that dispatch through apply_op but carry no
# sweep schema — each entry records WHY no deterministic single-device
# numpy oracle exists and WHERE the op is tested instead.  Bounded to
# <10% of the enumerated dispatch surface, like the reference's
# test/white_list discipline.
# ---------------------------------------------------------------------------
_COLLECTIVE = ("multi-device collective; loss-parity oracles in "
               "test_distributed.py / test_multiprocess_distributed.py")
_RANDOM = ("stochastic op (fresh PRNG key per call); distributional "
           "behavior tested in ")

NO_SCHEMA_WHITE_LIST = {
    # EVA chunked linearized attention (models/evabyte.py)
    "eva_attention": "dense window-plus-summaries attention; parity with "
                     "the plain reference in test_evabyte.py",
    # eager collectives / distributed-internal ops
    "all_reduce": _COLLECTIVE,
    "all_gather": _COLLECTIVE,
    "all_gather_concat": _COLLECTIVE,
    "all_to_all": _COLLECTIVE,
    "alltoall_single": _COLLECTIVE,
    "broadcast": _COLLECTIVE,
    "reduce_scatter": _COLLECTIVE,
    "ppermute": _COLLECTIVE,
    "local_slice": "sequence-parallel shard selector; parity in "
                   "test_sequence_parallel.py",
    "ring_attention": "sp-sharded attention over shard_map; vs-dense "
                      "parity in test_sequence_parallel.py",
    "ulysses_fwd": "all-to-all attention fwd; parity in "
                   "test_sequence_parallel.py",
    "ulysses_bwd": "all-to-all attention bwd; parity in "
                   "test_sequence_parallel.py",
    "vocab_parallel_embedding": "mp-sharded embedding; parity in "
                                "test_distributed.py",
    "moe_route": "EP routing (top-k gate); parity in test_moe.py",
    "expert_mlp": "per-expert MLP under shard_map; parity in test_moe.py",
    # stochastic ops: no deterministic oracle
    "gumbel_softmax": _RANDOM + "test_nn.py",
    "yolo_loss": "training composite (anchor assignment + 4 loss terms); "
                 "an independent numpy oracle would re-derive the whole "
                 "algorithm; unit tests in test_detection_ops.py",
    "class_center_sample": _RANDOM + "test_functional_extra.py",
    "top_p_sampling": _RANDOM + "test_generation.py",
    "normal_rsample": _RANDOM + "test_distribution.py",
    "gamma_rsample": _RANDOM + "test_distribution.py",
    "svd_lowrank": "randomized range-finder (fresh key); reconstruction "
                   "property tested in test_linalg_fft.py",
    "hsigmoid_loss": "heap-path host op; unit tests in "
                     "test_functional_extra.py",
    "deformable_conv": "offset-gather conv; unit tests in "
                       "test_functional_extra.py",
}
# Round 5: rope, repeat_kv, kv_cache_update, the RNN cells + fused RNN
# layers, ceil_pad, segment_mean_sum, sparse_linear_bias, getitem/setitem,
# the audio feature stages, flash attention (fwd sweep), fused MHA, and
# the MoE permutation dispatch/combine all moved OUT of this list into
# executable schemas (ops/schemas_round5.py). The survivors are
# collectives/shard_map per-rank programs (multi-device by nature) and
# stochastic ops — bounded at 5% of the dispatch surface
# (tests/test_schema_enforcement.py).

# round-5 conversions: registers schemas for the names pruned from
# NO_SCHEMA_WHITE_LIST above (import must precede the DYNAMIC_DISPATCH
# auto-whitelisting below so rnn_* resolve to their new schemas)
from . import schemas_round5  # noqa: E402,F401

# ---------------------------------------------------------------------------
# DYNAMIC_DISPATCH: the op-name SITES ops.audit cannot resolve statically.
# Each non-literal apply_op name must match one of these: an exact
# enumeration (the names also carry schemas where applicable) or an
# open prefix for user-defined op families.
# ---------------------------------------------------------------------------
DYNAMIC_DISPATCH = {
    "enumerated": {
        # fft.py wraps jnp.fft functions by __name__
        "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
        "fft2", "ifft2", "rfft2", "irfft2",
        "fftn", "ifftn", "rfftn", "irfftn", "hfftn", "ihfftn",
        # nn/layers_rnn.py: f"rnn_{mode.lower()}" — modes LSTM/GRU/RNN
        # (the runtime recorder caught "rnn_rnn"; activation is a cell
        # attr, not part of the mode string)
        "rnn_lstm", "rnn_gru", "rnn_rnn",
    },
    "prefixes": (
        "spmd:",     # distributed/collective.py shard_map programs
        "grad_",     # core/autograd.py grad-accumulation ops
        "custom_",   # utils/cpp_extension.py user custom ops
    ),
}

for _dyn_name in DYNAMIC_DISPATCH["enumerated"]:
    if _dyn_name not in SCHEMAS and _dyn_name not in NO_SCHEMA_WHITE_LIST:
        NO_SCHEMA_WHITE_LIST[_dyn_name] = (
            "rnn mode dispatch; torch-oracle parity in test_rnn.py")

# two more composites with independent numpy oracles (keeps
# NO_SCHEMA_WHITE_LIST under the 10% budget with margin)


def _hsigmoid_ref(x, lab, w, b):
    # complete binary heap, num_classes=4 -> depth 2, internal rows 0..2
    C = 4
    total = np.zeros((x.shape[0], 1), np.float32)
    for r in range(x.shape[0]):
        heap = int(lab[r]) + C
        path = []
        while heap > 1:
            path.append((heap // 2 - 1, heap & 1))
            heap //= 2
        for node, code in reversed(path):
            z = w[node] @ x[r] + b[node]
            sign = 2.0 * code - 1.0
            total[r, 0] += np.log1p(np.exp(-sign * z))
    return total


_S("hsigmoid_loss", _hsigmoid_ref,
   [((3, 5), "any"), ((3,), "idx3"), ((3, 5), "any"), ((3,), "any")],
   api="nn.functional.hsigmoid_loss",
   wrap=lambda api: lambda x, lab, w, b: api(x, lab, 4, w, b),
   grad_inputs=[0, 2, 3], tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC)


def _deform_conv_ref(x, off, w):
    N, Cin, H, W = x.shape
    Cout, _, kh, kw = w.shape
    Ho, Wo = H - kh + 1, W - kw + 1  # stride 1, pad 0, dilation 1
    offr = off.reshape(N, kh * kw, 2, Ho, Wo)
    out = np.zeros((N, Cout, Ho, Wo), np.float32)

    def bil(img, y, xx):
        if y < 0 or y > H - 1 or xx < 0 or xx > W - 1:
            return np.zeros(img.shape[0], np.float32)
        y0, x0 = int(np.floor(y)), int(np.floor(xx))
        y1, x1 = min(y0 + 1, H - 1), min(x0 + 1, W - 1)
        ly, lx = y - y0, xx - x0
        return (img[:, y0, x0] * (1 - ly) * (1 - lx)
                + img[:, y0, x1] * (1 - ly) * lx
                + img[:, y1, x0] * ly * (1 - lx)
                + img[:, y1, x1] * ly * lx)


    for n in range(N):
        for i in range(Ho):
            for j in range(Wo):
                acc = np.zeros((Cin, kh * kw), np.float32)
                for k in range(kh * kw):
                    ky, kx = k // kw, k % kw
                    acc[:, k] = bil(x[n], i + ky + offr[n, k, 0, i, j],
                                    j + kx + offr[n, k, 1, i, j])
                out[n, :, i, j] = np.einsum(
                    "ck,ock->o", acc, w.reshape(Cout, Cin, kh * kw))
    return out


_S("deformable_conv", _deform_conv_ref,
   [((1, 2, 5, 5), "any"), ((1, 8, 4, 4), "small"), ((3, 2, 2, 2), "any")],
   api="nn.functional.deformable_conv",
   grad_inputs=[0, 2], tol=_NN_TOL, grad_tol=_GRAD_TOL_ACC,
   dtypes=("float32",))

del NO_SCHEMA_WHITE_LIST["hsigmoid_loss"]
del NO_SCHEMA_WHITE_LIST["deformable_conv"]
