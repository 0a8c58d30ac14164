"""Eager (outside-spmd) cross-process collectives.

Parity: the reference's ProcessGroup task API executed from eager mode
(phi/core/distributed/collective/process_group.h:48-170; NCCL/Gloo
subclasses). TPU-native design (SURVEY §5.8): an eager collective is a
cached ONE-COLLECTIVE compiled program over the global process mesh —
each process contributes its local array as a shard of a stacked global
array, PJRT executes the compiled reduction/permutation, and the process
reads back its addressable shard. Rank = process (one participating
device per process, the reference's process-per-rank model).

These run on the Gloo-backed XLA CPU collectives in multi-process CPU
jobs and over ICI/DCN on TPU slices — same code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["process_world_size", "eager_all_reduce", "eager_broadcast",
           "eager_all_gather", "eager_reduce_scatter", "eager_alltoall",
           "eager_scatter", "eager_shift", "is_concrete",
           "coalescing_manager", "coalescing_active", "defer_all_reduce",
           "eager_all_reduce_coalesced"]


def process_world_size() -> int:
    return jax.process_count()


def is_concrete(arr) -> bool:
    """True when ``arr`` is a committed jax.Array (not a tracer) — the only
    case where a host-driven eager collective is possible."""
    return isinstance(arr, jax.Array) and not isinstance(arr, jax.core.Tracer)


@functools.lru_cache(maxsize=1)
def _world_mesh() -> Mesh:
    """One device per process, ordered by process index."""
    per_proc = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, []).append(d)
    devs = [sorted(per_proc[p], key=lambda d: d.id)[0]
            for p in sorted(per_proc)]
    return Mesh(np.array(devs), ("world",))


def _stacked_global(arr: jax.Array) -> jax.Array:
    """Build the global [W, *shape] array where slot p is process p's
    ``arr`` (the per-rank input of the collective)."""
    mesh = _world_mesh()
    W = mesh.devices.size
    sharding = NamedSharding(mesh, P("world"))
    local_dev = mesh.devices.flat[jax.process_index()]
    shard = jax.device_put(arr[None], local_dev)
    return jax.make_array_from_single_device_arrays(
        (W,) + tuple(arr.shape), sharding, [shard])


@functools.lru_cache(maxsize=256)
def _compiled(kind: str, shape, dtype, extra):
    """Cache of one-collective compiled programs keyed by op + aval."""
    mesh = _world_mesh()
    W = mesh.devices.size
    repl = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("world"))

    if kind in ("sum", "max", "min", "prod", "avg"):
        red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
               "prod": jnp.prod, "avg": jnp.mean}[kind]
        return jax.jit(lambda g: red(g, axis=0), out_shardings=repl)
    if kind in ("sum_block", "avg_block", "sum_strided", "avg_strided"):
        # grouped reductions for dp x pp process grids (round 5): the
        # world reshapes to (W//S, S). "block" groups are S consecutive
        # ranks (one pipeline replica's stages — tied-weight sums);
        # "strided" groups share rank % S (the same stage across data
        # replicas — dp grad sync). Every process enters the ONE
        # compiled program, so the lockstep deadlock-freedom argument is
        # unchanged; GSPMD lowers the segment reduction to collectives.
        S = extra
        red = jnp.mean if kind.startswith("avg") else jnp.sum

        def f(g):
            r = g.reshape((W // S, S) + tuple(shape))
            if kind.endswith("block"):
                out = jnp.repeat(red(r, axis=1, keepdims=True), S, axis=1)
            else:
                out = jnp.tile(red(r, axis=0, keepdims=True),
                               (W // S,) + (1,) * (r.ndim - 1))
            return out.reshape((W,) + tuple(shape))

        return jax.jit(f, out_shardings=sharded)
    if kind == "broadcast":
        src = extra
        return jax.jit(lambda g: g[src], out_shardings=repl)
    if kind == "broadcast_block":
        # rank r receives the row of (its block start + src_off)
        src_off, S = extra

        def f(g):
            r = g.reshape((W // S, S) + tuple(shape))
            out = jnp.repeat(r[:, src_off:src_off + 1], S, axis=1)
            return out.reshape((W,) + tuple(shape))

        return jax.jit(f, out_shardings=sharded)
    if kind == "all_gather":
        return jax.jit(lambda g: g, out_shardings=repl)
    if kind == "reduce_scatter":
        axis = extra
        # rank r's output = sum over ranks of slice r along ``axis``;
        # out sharded on world over that axis so each process reads its slice
        def f(g):
            s = jnp.sum(g, axis=0)
            return s
        out_spec = [None] * (len(shape))
        out_spec[axis] = "world"
        return jax.jit(f, out_shardings=NamedSharding(mesh, P(*out_spec)))
    if kind == "alltoall":
        split_axis, concat_axis = extra

        # g: [W_src(sharded), *shape] -> [W_dst, *shape'] where dst row r is
        # concat over src of each source's r-th split along concat_axis
        def f(g):
            parts = jnp.stack(jnp.split(g, W, axis=1 + split_axis), axis=0)
            # parts: [W_dst, W_src, *split_shape] (dst = split index)
            return jnp.concatenate([parts[:, i] for i in range(W)],
                                   axis=1 + concat_axis)

        return jax.jit(f, out_shardings=NamedSharding(mesh, P("world")))
    if kind == "shift":
        # p2p pipeline edge: rank r receives rank (r - shift)'s input;
        # edge ranks (no source) receive zeros. TRUE neighbor p2p: a
        # lax.ppermute over the world mesh — each payload moves along ONE
        # edge instead of the roll-over-gathered-world form (which was
        # all-gather-shaped: W x payload traffic). Deadlock-free for any
        # world size because every process enters the same collective
        # (the eager send/recv of the reference's ProcessGroup,
        # process_group.h send:129/recv:139 / pp_utils
        # p2p_communication.py:576 _p2p_helper).
        shift, block = extra if isinstance(extra, tuple) else (extra, None)
        perm = [(i, i + shift) for i in range(W)
                if 0 <= i + shift < W
                and (block is None or i // block == (i + shift) // block)]

        def body(local):  # [1, *shape] — this process's row
            return jax.lax.ppermute(local, "world", perm)

        f = jax.shard_map(body, mesh=mesh, in_specs=P("world"),
                          out_specs=P("world"))
        return jax.jit(f, out_shardings=NamedSharding(mesh, P("world")))
    if kind == "scatter":
        src, axis = extra
        def f(g):
            return g[src]
        out_spec = [None] * len(shape)
        out_spec[axis] = "world"
        return jax.jit(f, out_shardings=NamedSharding(mesh, P(*out_spec)))
    raise ValueError(kind)


def _run(kind: str, arr: jax.Array, extra=None) -> jax.Array:
    g = _stacked_global(arr)
    fn = _compiled(kind, tuple(arr.shape), str(arr.dtype), extra)
    out = fn(g)
    if kind in ("sum", "max", "min", "prod", "avg", "broadcast", "all_gather"):
        # fully replicated: our single addressable shard IS the result
        return out.addressable_shards[0].data
    # world-sharded outputs: our shard, leading collective axis dropped
    shard = out.addressable_shards[0].data
    return shard


def eager_all_reduce(arr, op: str = "sum"):
    if op not in ("sum", "max", "min", "prod", "avg"):
        raise ValueError(f"unsupported eager all_reduce op {op!r}")
    return _run(op, arr)


def eager_broadcast(arr, src: int = 0):
    return _run("broadcast", arr, src)


def eager_all_gather(arr):
    """Returns the stacked [W, *shape] result (replicated)."""
    return _run("all_gather", arr)


def eager_reduce_scatter(arr, axis: int = 0):
    return _run("reduce_scatter", arr, axis)


def eager_scatter(arr, src: int = 0, axis: int = 0):
    return _run("scatter", arr, (src, axis))


def eager_shift(arr, shift: int = 1, block: int = None):
    """Every process sends ``arr`` to rank+shift and receives from
    rank-shift (zeros past the edges). The pipeline p2p primitive.
    ``block``: edges stay within consecutive blocks of that size (one
    pipeline replica in a dp x pp grid)."""
    out = _run("shift", arr, (shift, block))
    return out[0] if out.ndim == arr.ndim + 1 else out


def eager_all_reduce_grouped(arr, group_size: int, mode: str = "block",
                             op: str = "sum"):
    """Reduce within process groups of a dp x pp grid. mode='block':
    groups are ``group_size`` consecutive ranks (a pipeline replica);
    mode='strided': groups share rank %% group_size (a stage's data
    replicas)."""
    assert mode in ("block", "strided") and op in ("sum", "avg")
    out = _run(f"{op}_{mode}", arr, group_size)
    return out[0] if out.ndim == arr.ndim + 1 else out


def eager_broadcast_block(arr, src_off: int, group_size: int):
    """Broadcast from the ``src_off``-th rank of each consecutive
    ``group_size`` block to its block peers."""
    out = _run("broadcast_block", arr, (src_off, group_size))
    return out[0] if out.ndim == arr.ndim + 1 else out


def eager_alltoall(arr, split_axis: int = 0, concat_axis: int = 0):
    out = _run("alltoall", arr, (split_axis, concat_axis))
    return out[0] if out.shape[0] == 1 else out


# ---------------------------------------------------------------------------
# coalescing (parity: process_group.h:119-123 StartCoalescing/EndCoalescing
# + collective/reducer.h:107 bucketed grad fusion). Individual eager
# all-reduces inside the context are deferred and flushed as ONE flat
# padded all-reduce per (op, dtype): the pad-to-power-of-two quantum makes
# the compiled-program count O(log max_payload) per world size instead of
# one program per distinct tensor shape.
# ---------------------------------------------------------------------------

_MIN_BUCKET = 1024  # elements


def _bucket_len(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def eager_all_reduce_coalesced(arrs, op: str = "sum"):
    """All-reduce a list of arrays (same dtype) as one flat padded
    collective; returns the reduced arrays in order."""
    if not arrs:
        return []
    shapes = [a.shape for a in arrs]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate([jnp.ravel(a) for a in arrs])
    total = flat.shape[0]
    padded = _bucket_len(total)
    if padded != total:
        # pad with identity-ish values; the tail is discarded on split
        flat = jnp.concatenate([flat, jnp.zeros((padded - total,), flat.dtype)])
    reduced = eager_all_reduce(flat, op)
    out, off = [], 0
    for s, n in zip(shapes, sizes):
        out.append(reduced[off:off + n].reshape(s))
        off += n
    return out


class _Coalescer:
    """Deferred entries hold a GETTER read at flush time (not a snapshot):
    grad accumulation finishing after the defer point is still captured.
    A key (tensor/param id) deduplicates; deferring the same tensor twice
    in one block would drop a reduction, so it raises instead."""

    def __init__(self):
        self.pending = []  # (getter, op, setter)
        self._seen: set = set()

    def add(self, key, getter, op: str, setter, on_dup: str = "error"):
        if key in self._seen:
            if on_dup == "skip":
                # flush-time getter reads the FINAL value, so one deferred
                # sync per key is exactly right (multi-contribution grads)
                return
            raise RuntimeError(
                "the same tensor was all-reduced twice inside one "
                "coalescing_manager block; compose reductions outside the "
                "block or use distinct tensors")
        self._seen.add(key)
        self.pending.append((getter, op, setter))

    def flush(self):
        groups = {}
        for getter, op, setter in self.pending:
            arr = getter()
            groups.setdefault((op, str(arr.dtype)), []).append((arr, setter))
        self.pending = []
        self._seen = set()
        for (op, _dt), items in groups.items():
            reduced = eager_all_reduce_coalesced([a for a, _ in items], op)
            for (_, setter), r in zip(items, reduced):
                setter(r)


_active: list = [None]


def coalescing_active() -> bool:
    return _active[0] is not None


def defer_all_reduce(key, getter, op: str, setter,
                     on_dup: str = "error") -> None:
    _active[0].add(key, getter, op, setter, on_dup)


class coalescing_manager:
    """``with coalescing_manager(): loss.backward()`` — every eager
    all_reduce issued inside (e.g. DataParallel grad hooks) is batched and
    flushed as flat bucketed collectives on exit."""

    def __enter__(self):
        if _active[0] is not None:
            raise RuntimeError("coalescing_manager does not nest")
        _active[0] = _Coalescer()
        return self

    def __exit__(self, exc_type, exc, tb):
        c, _active[0] = _active[0], None
        if exc_type is None:
            c.flush()
        return False
