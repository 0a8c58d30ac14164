"""Collective communication API.

Parity: python/paddle/distributed/communication/ (all_reduce, all_gather,
all_to_all, broadcast, reduce, reduce_scatter, scatter, send/recv,
barrier) + Group management (collective.py:151) + ReduceOp.

TPU-native design (SURVEY §5.8): collectives are *compiled*, not runtime
calls. The per-rank program model of the reference (each process runs the
same code on its local shard) maps to ``shard_map``: ``spmd(fn, mesh)``
runs ``fn`` once per mesh slot, and inside it these collective functions
lower to XLA collectives (psum/all_gather/ppermute) over ICI. Outside an
spmd region (plain eager, world of 1 process-local program) they are
identity ops on the single "rank", exactly like the reference with
world_size=1.
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from ..ops.dispatch import apply_op, ensure_tensor


def _eager_multiprocess(tensor: "Tensor", group: "Optional[Group]") -> bool:
    """True when an outside-spmd collective should execute as a cached
    one-collective program across processes (real multi-process world and a
    concrete — non-traced — array). Reference semantics: eager ProcessGroup
    collectives (process_group.h:48-170). Only the world group is
    supported eagerly; a proper subgroup raises instead of silently
    reducing over the wrong ranks (or deadlocking non-members)."""
    from . import eager_collectives as ec

    if ec.process_world_size() <= 1 or not ec.is_concrete(tensor._data):
        return False
    if group is not None and group.id != 0:
        W = ec.process_world_size()
        if not group.ranks or sorted(group.ranks) != list(range(W)):
            # includes rank-less named-axis groups: outside spmd their
            # membership is undefined, so treating them as world would
            # silently reduce over the wrong ranks
            raise NotImplementedError(
                "eager (outside-spmd) collectives over a proper subgroup are "
                "not supported — run subgroup collectives inside dist.spmd "
                "over a mesh axis, or use the world group")
    return True


def _eager_result(tensor: "Tensor", data) -> "Tensor":
    """In-place update with the collective result, preserving autograd
    leaf-ness (reference eager comm ops mutate the tensor's storage and do
    not change requires_grad). The grad node is dropped: the result's
    history crosses processes (not representable on the local tape), and a
    shape-changing collective (scatter) would otherwise leave a stale
    full-shape node that corrupts a later backward."""
    sg = tensor.stop_gradient
    tensor._data = data
    tensor._grad_node = None
    tensor._out_slot = None
    tensor.stop_gradient = sg
    return tensor


_OP_NAMES = {0: "sum", 1: "max", 2: "min", 3: "prod", 4: "avg"}


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A communicator: a named mesh axis (TPU-native 'ring').

    Parity: python/paddle/distributed/communication/group.py Group. Inside
    spmd regions the axis name selects which mesh dimension the collective
    runs over (= the reference's ring id / process group)."""

    _next_gid = [0]

    def __init__(self, axis_name: Optional[str] = None, ranks: Optional[List[int]] = None, gid=None):
        if gid is None:
            Group._next_gid[0] += 1
            gid = Group._next_gid[0]
        self.id = gid
        self.axis_name = axis_name
        self.ranks = ranks or []

    @property
    def nranks(self):
        ctx = _current_spmd()
        if ctx is not None and self.axis_name in ctx.mesh.axis_names:
            return ctx.mesh.shape[self.axis_name]
        return len(self.ranks) or 1

    world_size = nranks

    @property
    def rank(self):
        ctx = _current_spmd()
        if ctx is not None and self.axis_name in ctx.mesh.axis_names:
            return jax.lax.axis_index(self.axis_name)
        return 0

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def process_ids(self):
        return self.ranks


_tls = threading.local()


class _SpmdCtx:
    def __init__(self, mesh: Mesh, axis_names):
        self.mesh = mesh
        self.axis_names = axis_names


def _current_spmd() -> Optional[_SpmdCtx]:
    stack = getattr(_tls, "spmd_stack", None)
    return stack[-1] if stack else None


_WORLD = Group(axis_name="world", gid=0)
_groups = {0: _WORLD}


def get_group(gid=0) -> Group:
    return _groups.get(gid, _WORLD)


def new_group(ranks=None, backend=None, timeout=None, axis_name: Optional[str] = None) -> Group:
    g = Group(axis_name=axis_name or f"group{Group._next_gid[0] + 1}", ranks=ranks)
    _groups[g.id] = g
    return g


def _axis(group: Optional[Group]):
    ctx = _current_spmd()
    if ctx is None:
        return None
    g = group or _WORLD
    if g.axis_name in ctx.mesh.axis_names:
        return g.axis_name
    if g.axis_name == "world":
        # world group inside spmd = all mesh axes
        return tuple(ctx.axis_names)
    return None


def spmd(fn: Callable, mesh, in_specs=None, out_specs=None, check_vma=False):
    """Run ``fn`` as a per-rank program over ``mesh`` (the TPU-native
    equivalent of launching one process per rank). ``fn`` receives Tensors
    holding this rank's local shard; collective functions inside lower to
    XLA collectives.

    mesh: jax Mesh, ProcessMesh, or dict {axis: size}.
    """
    from .mesh import ProcessMesh

    if isinstance(mesh, ProcessMesh):
        jmesh = mesh.jax_mesh
    elif isinstance(mesh, dict):
        devs = np.array(jax.devices()[: int(np.prod(list(mesh.values())))])
        jmesh = Mesh(devs.reshape(tuple(mesh.values())), axis_names=tuple(mesh.keys()))
    else:
        jmesh = mesh
    axis_names = tuple(jmesh.axis_names)

    def wrapper(*args, **kwargs):
        spec_in = in_specs if in_specs is not None else PartitionSpec(axis_names)
        spec_out = out_specs if out_specs is not None else PartitionSpec(axis_names)

        # Flatten arbitrary pytree args (Tensors as leaves) to a flat tensor
        # list so the program can route through the dispatch layer as ONE
        # tape node — gradients then flow through shard_map via jax.vjp.
        is_t = lambda x: isinstance(x, Tensor)
        flat_args, in_tree = jax.tree.flatten(args, is_leaf=is_t)
        tensor_args = [a if isinstance(a, Tensor) else Tensor(jnp.asarray(a)) for a in flat_args]
        out_tree_cell = []

        def inner(*datas):
            stack = getattr(_tls, "spmd_stack", None)
            if stack is None:
                stack = _tls.spmd_stack = []
            stack.append(_SpmdCtx(jmesh, axis_names))
            try:
                targs = jax.tree.unflatten(in_tree, [Tensor(d) for d in datas])
                out = fn(*targs, **kwargs)
                out_datas = jax.tree.map(lambda t: t._data if isinstance(t, Tensor) else t, out,
                                         is_leaf=is_t)
                flat_out, out_tree = jax.tree.flatten(out_datas)
                out_tree_cell.clear()
                out_tree_cell.append(out_tree)
                return tuple(flat_out) if len(flat_out) != 1 else flat_out[0]
            finally:
                stack.pop()

        sm = jax.shard_map(inner, mesh=jmesh, in_specs=spec_in,
                           out_specs=spec_out, check_vma=check_vma)
        from ..ops.dispatch import apply_op

        outs = apply_op(f"spmd:{getattr(fn, '__name__', 'program')}", sm, *tensor_args)
        out_tree = out_tree_cell[0]
        flat_outs = outs if isinstance(outs, (list, tuple)) else [outs]
        return jax.tree.unflatten(out_tree, list(flat_outs))

    return wrapper


# ---------------------------------------------------------------------------
# Collectives (usable inside spmd regions; identity at world_size==1 outside)
# ---------------------------------------------------------------------------


def _reduce_fn(op):
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        return jax.lax.psum
    if op == ReduceOp.MAX:
        return jax.lax.pmax
    if op == ReduceOp.MIN:
        return jax.lax.pmin
    raise ValueError(f"unsupported reduce op {op}")


def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op=True):
    ax = _axis(group)
    if ax is None:
        if _eager_multiprocess(tensor, group):
            from . import eager_collectives as ec

            if ec.coalescing_active():
                # deferred: the coalescer reads tensor._data at FLUSH time
                # and rebinds it with the reduced payload at context exit
                # (StartCoalescing semantics)
                ec.defer_all_reduce(
                    id(tensor),
                    lambda _t=tensor: _t._data, _OP_NAMES[op],
                    lambda data, _t=tensor: _eager_result(_t, data))
                return tensor
            return _eager_result(tensor, ec.eager_all_reduce(tensor._data, _OP_NAMES[op]))
        return tensor
    f = _reduce_fn(op)

    def _f(x):
        out = f(x, ax)
        if op == ReduceOp.AVG:
            n = jax.lax.psum(jnp.ones((), x.dtype), ax)
            out = out / n
        return out

    out = apply_op("all_reduce", _f, tensor)
    tensor._replace_(out)
    return tensor


def all_gather(tensor_list, tensor: Tensor = None, group: Optional[Group] = None, sync_op=True, axis=0):
    """Paddle signature: all_gather(tensor_list, tensor). Returns the list
    of per-rank tensors; inside spmd it lowers to lax.all_gather."""
    if isinstance(tensor_list, Tensor) and tensor is None:
        # functional form: return stacked gather
        tensor, tensor_list = tensor_list, None
    ax = _axis(group)
    if ax is None:
        if _eager_multiprocess(tensor, group):
            from . import eager_collectives as ec
            from ..ops.manipulation import unstack

            stacked = Tensor(ec.eager_all_gather(tensor._data))
            if tensor_list is not None:
                tensor_list.extend(unstack(stacked, axis=0))
                return tensor_list
            return stacked
        if tensor_list is not None:
            tensor_list.append(tensor.clone())
            return tensor_list
        return tensor
    out = apply_op("all_gather", lambda x: jax.lax.all_gather(x, ax), tensor)
    if tensor_list is not None:
        n = (group or _WORLD).nranks
        from ..ops.manipulation import unstack

        parts = unstack(out, axis=0)
        tensor_list.extend(parts)
        return tensor_list
    return out


def all_gather_concat(tensor: Tensor, group: Optional[Group] = None, axis: int = 0):
    """TPU-native convenience: gather and concat along ``axis`` (the common
    SP/TP pattern; reference: mp_ops._c_concat)."""
    ax = _axis(group)
    if ax is None:
        return tensor
    return apply_op("all_gather_concat", lambda x: jax.lax.all_gather(x, ax, axis=axis, tiled=True), tensor)


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op=True, axis=0):
    ax = _axis(group)
    if ax is None:
        if _eager_multiprocess(tensor, group):
            if op != ReduceOp.SUM:
                raise ValueError(
                    "eager reduce_scatter supports ReduceOp.SUM only "
                    "(XLA psum_scatter semantics); got op=%r" % (op,))
            from . import eager_collectives as ec

            return Tensor(ec.eager_reduce_scatter(tensor._data, axis))
        return tensor
    return apply_op("reduce_scatter", lambda x: jax.lax.psum_scatter(x, ax, scatter_dimension=axis, tiled=True), tensor)


def broadcast(tensor: Tensor, src: int = 0, group: Optional[Group] = None, sync_op=True):
    ax = _axis(group)
    if ax is None:
        if _eager_multiprocess(tensor, group):
            from . import eager_collectives as ec

            return _eager_result(tensor, ec.eager_broadcast(tensor._data, src))
        return tensor

    def _f(x):
        # take src's value on every rank: gather then select (XLA folds this
        # into a broadcast collective)
        full = jax.lax.all_gather(x, ax)
        return full[src]

    out = apply_op("broadcast", _f, tensor)
    tensor._replace_(out)
    return tensor


def reduce(tensor: Tensor, dst: int = 0, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op=True):
    # On TPU every rank gets the reduction (all_reduce); dst semantics kept
    # by callers ignoring non-dst results (reference reduce is rarely used
    # without a following broadcast).
    return all_reduce(tensor, op=op, group=group)


def scatter(tensor: Tensor, tensor_list=None, src=0, group: Optional[Group] = None, sync_op=True):
    ax = _axis(group)
    if ax is None:
        if _eager_multiprocess(tensor, group):
            from . import eager_collectives as ec

            return _eager_result(tensor, ec.eager_scatter(tensor._data, src))
        return tensor
    g = group or _WORLD

    def _f(x):
        full = jax.lax.all_gather(x, ax)  # [n, ...] everyone sees src's data at [src]
        idx = jax.lax.axis_index(ax)
        n = full.shape[0]
        srcdata = full[src]
        per = srcdata.shape[0] // n
        return jax.lax.dynamic_slice_in_dim(srcdata, idx * per, per, axis=0)

    out = apply_op("scatter", _f, tensor)
    tensor._replace_(out)
    return tensor


def all_to_all(out_tensor_list, in_tensor_list=None, group: Optional[Group] = None, sync_op=True):
    """Paddle signature: lists of per-rank tensors. Inside spmd, prefer
    ``alltoall_single``/``alltoall`` on a stacked tensor (lax.all_to_all)."""
    if isinstance(out_tensor_list, Tensor):
        return alltoall_single(out_tensor_list, group=group)
    ax = _axis(group)
    if ax is None:
        out_tensor_list.extend(t.clone() for t in in_tensor_list)
        return out_tensor_list
    from ..ops.manipulation import stack, unstack

    stacked = stack(in_tensor_list, axis=0)
    out = apply_op("all_to_all", lambda x: jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0, tiled=False), stacked)
    out_tensor_list.extend(unstack(out, axis=0))
    return out_tensor_list


def alltoall_single(tensor: Tensor, output=None, in_split_sizes=None, out_split_sizes=None,
                    group: Optional[Group] = None, sync_op=True, split_axis=0, concat_axis=0):
    def _uneven(sizes):
        return sizes is not None and len(set(sizes)) > 1

    if _uneven(in_split_sizes) or _uneven(out_split_sizes):
        raise NotImplementedError(
            "alltoall_single with UNEVEN split sizes is not implemented; "
            "pad to equal splits (XLA all-to-all requires them). Equal "
            "explicit splits are accepted.")
    ax = _axis(group)
    if ax is None:
        if _eager_multiprocess(tensor, group):
            from . import eager_collectives as ec

            return Tensor(ec.eager_alltoall(tensor._data, split_axis, concat_axis))
        return tensor
    return apply_op(
        "alltoall_single",
        lambda x: jax.lax.all_to_all(x, ax, split_axis=split_axis, concat_axis=concat_axis, tiled=True),
        tensor,
    )


def local_slice(tensor: Tensor, dim: int, group: Optional[Group] = None) -> Tensor:
    """This rank's slice of a replicated tensor along ``dim`` (the shared
    per-rank shard recipe used by TP layers and sequence-parallel scatter).
    No-ops outside spmd or when the group's axis isn't bound on the mesh.
    Requires the dimension to divide the group size."""
    ax = _axis(group)
    if ax is None or isinstance(ax, tuple):
        return tensor
    g = group or _WORLD
    n = g.nranks
    size = tensor._data.shape[dim]
    if size % n != 0:
        raise ValueError(
            f"local_slice: dim {dim} of size {size} not divisible by group size {n} "
            "(reference asserts divisibility at layer construction)")

    def _f(a):
        idx = jax.lax.axis_index(ax)
        per = a.shape[dim] // n
        return jax.lax.dynamic_slice_in_dim(a, idx * per, per, axis=dim)

    return apply_op("local_slice", _f, tensor)


def ppermute(tensor: Tensor, perm, group: Optional[Group] = None):
    """collective-permute (TPU-native P2P: reference isend/irecv pairs map
    to ppermute rings on ICI; reference: pp_utils/p2p_communication.py)."""
    ax = _axis(group)
    if ax is None:
        return tensor
    return apply_op("ppermute", lambda x: jax.lax.ppermute(x, ax, perm), tensor)


_P2P_SPMD_MSG = (
    "point-to-point send/recv inside an SPMD program must be expressed as a "
    "permutation: use paddle_tpu.distributed.ppermute (XLA collective-permute); "
    "per-pair send/recv is not a compilable TPU primitive")


def _eager_p2p_applies(tensor: Tensor, group, peer: int, role: str) -> bool:
    """Gate for the eager 2-process p2p path. Misuse raises — a silent
    no-op here would hand the caller an unfilled receive buffer."""
    from . import eager_collectives as ec

    if ec.process_world_size() <= 1 or not ec.is_concrete(tensor._data):
        return False
    _eager_multiprocess(tensor, group)  # raises on proper subgroups
    W = ec.process_world_size()
    if W != 2:
        raise NotImplementedError(
            f"eager send/recv is supported for 2-process worlds (the pair IS "
            f"the world, so it compiles as one matched broadcast); with "
            f"{W} processes route p2p through dist.eager_shift or ppermute")
    me = jax.process_index()
    if peer == me or peer not in (0, 1):
        raise ValueError(
            f"{role}={peer} is invalid for rank {me} in a 2-process world "
            "(the peer must be the other rank)")
    return True


def send(tensor: Tensor, dst=0, group: Optional[Group] = None, sync_op=True):
    """Eager p2p (parity: distributed/communication/send.py). In a
    2-process world send/recv execute as one matched broadcast-shaped
    compiled program (sender = source row)."""
    ctx = _current_spmd()
    if ctx is None:
        if _eager_p2p_applies(tensor, group, dst, "dst"):
            from . import eager_collectives as ec

            ec.eager_broadcast(tensor._data, src=jax.process_index())
        return tensor
    raise RuntimeError(_P2P_SPMD_MSG)


def recv(tensor: Tensor, src=0, group: Optional[Group] = None, sync_op=True):
    ctx = _current_spmd()
    if ctx is None:
        if _eager_p2p_applies(tensor, group, src, "src"):
            from . import eager_collectives as ec

            return _eager_result(tensor,
                                 ec.eager_broadcast(tensor._data, src=src))
        return tensor
    raise RuntimeError(_P2P_SPMD_MSG)


isend = send
irecv = recv


def barrier(group: Optional[Group] = None):
    ax = _axis(group)
    if ax is None:
        # host-level barrier across processes
        try:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("paddle_tpu_barrier")
        except Exception:
            pass
        return
    return None  # inside a compiled program every rank is already in lockstep


def destroy_process_group(group=None):
    if group is not None:
        _groups.pop(group.id, None)
    else:
        _groups.clear()
        _groups[0] = _WORLD


# stream namespace parity (paddle.distributed.stream.*)
class stream:
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    broadcast = staticmethod(broadcast)
    scatter = staticmethod(scatter)
    alltoall = staticmethod(all_to_all)
    alltoall_single = staticmethod(alltoall_single)
    send = staticmethod(send)
    recv = staticmethod(recv)
    reduce = staticmethod(reduce)
