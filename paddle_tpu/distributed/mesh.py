"""ProcessMesh + placements: the semi-auto-parallel substrate.

Parity: the reference's auto_parallel core —
phi/core/distributed/auto_parallel/placement_types.h:36 (Placement,
Shard:68, Replicate:108, Partial:132), process_mesh.h ProcessMesh,
dist_tensor.h:39 DistTensor.

TPU design: ProcessMesh wraps jax.sharding.Mesh; placements translate
directly to NamedSharding PartitionSpecs. GSPMD then plays the role of the
reference's SPMD rules + reshard engine: annotate, and XLA inserts the
collectives (SURVEY §7.1 table).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


class Placement:
    def is_shard(self, dim=None):
        return False

    def is_replicate(self):
        return False

    def is_partial(self):
        return False


class Shard(Placement):
    def __init__(self, dim: int):
        self.dim = dim

    def is_shard(self, dim=None):
        return dim is None or dim == self.dim

    def get_dim(self):
        return self.dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("shard", self.dim))


class Replicate(Placement):
    def is_replicate(self):
        return True

    def __repr__(self):
        return "Replicate()"

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("replicate")


class Partial(Placement):
    """Pending-reduction placement. In XLA terms this only exists transiently
    inside computations (psum not yet applied); reshard(Partial->Replicate)
    lowers to an all-reduce (reference: p_to_r_reshard_function.cc)."""

    def __init__(self, reduce_type="sum"):
        self.reduce_type = reduce_type

    def is_partial(self):
        return True

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, other):
        return isinstance(other, Partial) and other.reduce_type == self.reduce_type

    def __hash__(self):
        return hash(("partial", self.reduce_type))


class ProcessMesh:
    """Parity: paddle.distributed.ProcessMesh(mesh, dim_names).

    Backed by jax.sharding.Mesh over the PJRT devices the slots name
    (by id, or by rank in id order).
    """

    def __init__(self, mesh, dim_names: Optional[Sequence[str]] = None, shape=None, process_ids=None):
        arr = np.asarray(mesh, dtype=np.int64)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        self._shape = tuple(arr.shape)
        self._dim_names = tuple(dim_names)
        self._process_ids = arr
        # slot values are device ids where every one of them is (one
        # process, a TPU slice, create_hybrid_mesh), else ranks into the
        # id-ordered device list (fleet topologies: multi-process CPU
        # device ids run 0, 2048, ..)
        devices = sorted(jax.devices(), key=lambda d: d.id)
        by_id = {d.id: d for d in devices}
        slots = {int(i) for i in arr.reshape(-1)}
        if slots <= set(by_id):
            pick = by_id.__getitem__
        elif all(0 <= i < len(devices) for i in slots):
            pick = devices.__getitem__
        else:
            raise ValueError(
                f"ProcessMesh slots {sorted(slots)} are neither device ids "
                f"({sorted(by_id)}) nor ranks below {len(devices)}: a mesh "
                f"slot never aliases another slot's device")
        dev_arr = np.vectorize(lambda i: pick(int(i)))(arr)
        self._jax_mesh = Mesh(dev_arr, axis_names=self._dim_names)

    @property
    def shape(self):
        return list(self._shape)

    @property
    def ndim(self):
        return len(self._shape)

    @property
    def dim_names(self):
        return list(self._dim_names)

    @property
    def process_ids(self):
        return self._process_ids.reshape(-1).tolist()

    @property
    def jax_mesh(self) -> Mesh:
        return self._jax_mesh

    def get_dim_size(self, name: str) -> int:
        return self._shape[self._dim_names.index(name)]

    def get_rank_by_dim_and_process_id(self, dim_name, process_id):
        axis = self._dim_names.index(dim_name)
        pos = np.argwhere(self._process_ids == process_id)
        return int(pos[0][axis]) if len(pos) else -1

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh) and self._shape == other._shape
                and self._dim_names == other._dim_names
                and np.array_equal(self._process_ids, other._process_ids))

    def __hash__(self):
        return hash((self._shape, self._dim_names, self._process_ids.tobytes()))

    def __repr__(self):
        return f"ProcessMesh(shape={list(self._shape)}, dim_names={list(self._dim_names)})"


def create_hybrid_mesh(dim_names: Sequence[str], ici_shape: Sequence[int],
                       dcn_shape: Sequence[int],
                       process_is_granule: Optional[bool] = None) -> ProcessMesh:
    """DCN-spanning ProcessMesh for multi-slice / multi-host pods.

    Each named axis decomposes into an intra-slice (ICI) part and a
    cross-slice (DCN) part: ``axis size = ici_shape[i] * dcn_shape[i]``.
    Devices are arranged with jax mesh_utils.create_hybrid_device_mesh so
    collectives on a dcn-decomposed axis cross DCN exactly once per hop
    while ici-only axes never leave the slice — the device-assignment
    form of the reference's multi-node topology (fleet/base/topology.py
    CommunicateTopology nodes x devices; SURVEY §5.8 "DCN-spanning
    meshes"). The canonical layout shards dp (and pp) over dcn and keeps
    mp/sp inside a slice:

        mesh = create_hybrid_mesh(["dp", "mp"], ici_shape=[1, 4],
                                  dcn_shape=[2, 1])   # 2 slices x 4 chips

    ``process_is_granule``: treat one PROCESS as the DCN granule instead
    of one TPU slice — the layout rule for CPU pods and for GPU-style
    one-process-per-host deployments. Default: auto — slice granules
    when the backend reports more than one slice, process granules
    otherwise (single-slice and CPU backends report slice_index 0
    everywhere, so the process boundary is the only DCN boundary)."""
    if len(dim_names) != len(ici_shape) or len(ici_shape) != len(dcn_shape):
        raise ValueError(
            f"dim_names/ici_shape/dcn_shape must align: "
            f"{len(dim_names)}/{len(ici_shape)}/{len(dcn_shape)}")
    from jax.experimental import mesh_utils

    devices = jax.devices()
    total = int(np.prod(ici_shape)) * int(np.prod(dcn_shape))
    if total != len(devices):
        raise ValueError(
            f"mesh wants {total} devices, backend has {len(devices)}")
    if process_is_granule is None:
        slices = {getattr(d, "slice_index", None) for d in devices}
        process_is_granule = len(slices - {None}) <= 1
    if int(np.prod(dcn_shape)) == 1:
        # degenerate single-granule case: plain device mesh (the hybrid
        # helper requires >=2 granules to infer the DCN dimension)
        dev_arr = mesh_utils.create_device_mesh(
            tuple(ici_shape), devices=devices)
    else:
        dev_arr = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape), tuple(dcn_shape), devices=devices,
            process_is_granule=process_is_granule)
    ids = np.vectorize(lambda d: d.id)(dev_arr)
    return ProcessMesh(ids, list(dim_names))


def placements_to_spec(placements: Sequence[Placement], mesh: ProcessMesh, ndim: int) -> PartitionSpec:
    """Translate a placement list (one entry per mesh dim, reference
    semantics) into a PartitionSpec over tensor dims."""
    entries: List[Optional[object]] = [None] * ndim
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            axis_name = mesh.dim_names[mesh_dim]
            cur = entries[pl.dim]
            if cur is None:
                entries[pl.dim] = axis_name
            elif isinstance(cur, tuple):
                entries[pl.dim] = cur + (axis_name,)
            else:
                entries[pl.dim] = (cur, axis_name)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def spec_to_placements(spec: PartitionSpec, mesh: ProcessMesh, ndim: int) -> List[Placement]:
    placements: List[Placement] = [Replicate() for _ in range(mesh.ndim)]
    for tensor_dim, entry in enumerate(spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        for name in names:
            placements[mesh.dim_names.index(name)] = Shard(tensor_dim)
    return placements


def named_sharding(mesh: ProcessMesh, placements: Sequence[Placement], ndim: int) -> NamedSharding:
    return NamedSharding(mesh.jax_mesh, placements_to_spec(placements, mesh, ndim))
