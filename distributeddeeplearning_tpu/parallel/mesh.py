"""Device mesh construction.

The reference's process geometry is ``node_count × process_count_per_node=4``
MPI ranks with one GPU pinned per rank (``control/src/aml_compute.py:108-133``,
``resnet_main.py:142-145``).  On TPU the geometry is a *logical mesh* over the
pod slice: one named axis per parallelism strategy, with XLA laying the
resulting collectives onto ICI (within-slice) / DCN (across-slice) links.

Axis convention (fixed names, used by every sharding rule in the framework):

    data    — data parallelism (gradient psum), the reference's only strategy
    fsdp    — parameter/optimizer sharding along the data axis (ZeRO-style)
    tensor  — tensor/model parallelism (activations + weight shards)
    seq     — sequence/context parallelism (ring attention)
    expert  — expert parallelism for MoE layers
    pipe    — pipeline parallelism stages

A ``MeshSpec`` names the per-axis sizes; unspecified axes default to 1 and
``data`` absorbs the remaining devices, so ``MeshSpec()`` on N chips is pure
DP over N — exactly the reference's semantics (Horovod world = all GPUs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order: outermost (slowest-varying, crosses DCN first) to
# innermost (fastest-varying, stays on ICI).  Data-parallel gradients tolerate
# slow links best, tensor-parallel activations worst — so data/pipe go
# outermost and tensor/seq innermost, matching the scaling-book recipe.
AXIS_ORDER: Tuple[str, ...] = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

DATA_AXES: Tuple[str, ...] = ("data", "fsdp")  # batch is sharded over both


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh geometry.  Any axis left at None is inferred.

    At most one axis may be None; it absorbs ``device_count // product(rest)``.
    With every axis None-free the product must equal the device count.
    If all axes are concrete sizes of 1 except none, ``data`` defaults to None
    (absorbs everything) — i.e. ``MeshSpec()`` is full data parallelism.
    """

    pipe: Optional[int] = 1
    data: Optional[int] = None
    fsdp: Optional[int] = 1
    expert: Optional[int] = 1
    seq: Optional[int] = 1
    tensor: Optional[int] = 1

    def sizes(self, device_count: int) -> Tuple[int, ...]:
        raw = [getattr(self, name) for name in AXIS_ORDER]
        free = [i for i, s in enumerate(raw) if s is None]
        if len(free) > 1:
            raise ValueError(f"At most one mesh axis may be None, got {free}")
        known = math.prod(s for s in raw if s is not None)
        if free:
            if device_count % known != 0:
                raise ValueError(
                    f"{device_count} devices not divisible by fixed axes product {known}"
                )
            raw[free[0]] = device_count // known
        elif known != device_count:
            raise ValueError(
                f"Mesh axes product {known} != device count {device_count}"
            )
        return tuple(raw)  # type: ignore[return-value]


def _slice_groups(devices: Sequence[jax.Device], num_slices: int):
    """Group devices by TPU slice.

    Real multi-slice deployments expose ``Device.slice_index``; CPU fakes
    (and single-slice pods) don't, so an explicit ``num_slices`` falls back
    to contiguous equal splits — structurally identical, which is what the
    virtual-pod tests exercise.
    """
    indices = [getattr(d, "slice_index", None) for d in devices]
    if all(i is not None for i in indices):
        distinct = len(set(indices))
        if distinct != num_slices:
            # Known physical topology contradicting the request must not be
            # silently discarded: a contiguous fallback would place ICI-only
            # collectives across DCN — the exact failure this mesh prevents.
            raise ValueError(
                f"devices report {distinct} physical slice(s) but "
                f"num_slices={num_slices} was requested"
            )
        groups: dict = {}
        for d, i in zip(devices, indices):
            groups.setdefault(i, []).append(d)
        return [groups[i] for i in sorted(groups)]
    if len(devices) % num_slices:
        raise ValueError(
            f"{len(devices)} devices not divisible into {num_slices} slices"
        )
    per = len(devices) // num_slices
    return [list(devices[i * per:(i + 1) * per]) for i in range(num_slices)]


def create_mesh(
    spec: Optional[MeshSpec] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    num_slices: int = 1,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` for ``spec`` over ``devices``.

    Replaces Horovod's implicit world: the reference gets its communicator
    from ``hvd.init()`` (``resnet_main.py:232``); here the mesh *is* the
    communicator, and every collective in the train step is expressed against
    its named axes.  On TPU ``jax.experimental.mesh_utils`` orders the devices
    so that the mesh respects the physical topology (ICI neighbours stay
    mesh-adjacent).

    ``num_slices > 1`` builds a **multi-slice (DCN) mesh**: the ``data``
    axis's outermost component spans slices, so the only cross-slice
    collective is the gradient psum (data parallelism tolerates DCN latency;
    fsdp/tensor/seq/expert stay on each slice's ICI — the scaling-book
    multi-slice recipe).  The data axis size must be a multiple of
    ``num_slices``; slice membership comes from ``Device.slice_index`` when
    the runtime exposes it, else contiguous split (CPU-fake structural mode).
    """
    spec = spec or MeshSpec()
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    sizes = spec.sizes(len(devices))
    if num_slices > 1:
        data_pos = AXIS_ORDER.index("data")
        data_size = sizes[data_pos]
        if data_size % num_slices:
            raise ValueError(
                f"data axis {data_size} not divisible by num_slices "
                f"{num_slices} — multi-slice runs scale data parallelism "
                "across DCN"
            )
        groups = _slice_groups(devices, num_slices)
        # Per-slice sub-mesh (ICI-aware), then stack along the data axis so
        # index order puts the slice boundary outermost on `data`.
        sub = [
            create_mesh(
                _spec_with(spec, data=data_size // num_slices),
                devices=g,
            ).devices
            for g in groups
        ]
        dev_array = np.concatenate(sub, axis=data_pos)
        return Mesh(dev_array, AXIS_ORDER)
    if all(d.platform == "tpu" for d in devices):
        # topology-aware or not at all: an enumeration-order layout would
        # run, with collectives off their ICI neighbours and nothing in
        # any report to say so — a shape mesh_utils cannot place raises
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            sizes, devices=devices, allow_split_physical_axes=True
        )
    else:
        # CPU/GPU fakes have no ICI topology; plain reshape is exact.
        dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, AXIS_ORDER)


def _spec_with(spec: MeshSpec, **overrides) -> MeshSpec:
    return dataclasses.replace(spec, **overrides)


def world_size(mesh: Optional[Mesh] = None) -> int:
    """Total device count — the reference's ``hvd.size()``."""
    if mesh is None:
        return jax.device_count()
    return mesh.devices.size


def data_parallel_size(mesh: Mesh) -> int:
    """Number of data-parallel replicas (batch shards): data × fsdp."""
    return int(np.prod([mesh.shape[a] for a in DATA_AXES]))


def local_device_count() -> int:
    """Devices attached to this host — the reference's GPUs-per-node=4
    (``aml_compute.py:108-109``)."""
    return jax.local_device_count()
