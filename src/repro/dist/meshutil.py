"""Local mesh construction for launchers, tests, and CI.

``local_mesh`` builds a device mesh from a settings-style ``mesh_shape`` over
whatever devices this process has — one CPU in unit tests, eight forced host
devices in the multi-device tests, real accelerators in production — with clear
errors when the requested shape cannot be satisfied.  ``remove_host`` is the
eviction rebuild: the same mesh minus one slice along an axis, used by the
straggler-response controller when a host is pulled from the fleet.
"""

from __future__ import annotations

import math
from collections.abc import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["local_mesh", "default_axis_names", "pipeline_submeshes", "remove_host"]

_AXIS_NAMES_BY_RANK = {
    1: ("data",),
    2: ("data", "model"),
    3: ("pod", "data", "model"),
}


def default_axis_names(rank: int) -> tuple[str, ...]:
    """Conventional axis names for a mesh of the given rank."""
    if rank not in _AXIS_NAMES_BY_RANK:
        raise ValueError(
            f"no default axis names for a rank-{rank} mesh; pass axis_names "
            f"explicitly (defaults exist for ranks {sorted(_AXIS_NAMES_BY_RANK)})"
        )
    return _AXIS_NAMES_BY_RANK[rank]


def local_mesh(
    mesh_shape: Sequence[int] = (1, 1),
    axis_names: Sequence[str] | None = None,
) -> Mesh:
    """Build a mesh of ``mesh_shape`` from this process's devices.

    CPU-friendly: a ``(1, 1)`` shape on a single-CPU host yields a 1-device
    ``("data", "model")`` mesh, so the same launcher code path runs in CI and
    at scale.  Uses the first ``prod(mesh_shape)`` devices, so a smaller mesh
    than the host's device count is allowed.
    """
    shape = tuple(int(s) for s in mesh_shape)
    if not shape or any(s < 1 for s in shape):
        raise ValueError(f"mesh_shape must be non-empty positive ints, got {mesh_shape!r}")
    names = tuple(axis_names) if axis_names is not None else default_axis_names(len(shape))
    if len(names) != len(shape):
        raise ValueError(f"axis_names {names} does not match mesh_shape {shape}")
    n_needed = math.prod(shape)
    devices = jax.devices()
    if n_needed > len(devices):
        raise ValueError(
            f"mesh_shape {shape} needs {n_needed} devices but only "
            f"{len(devices)} are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_needed} for CPU runs"
        )
    return jax.make_mesh(shape, names, devices=devices[:n_needed],
                         axis_types=(AxisType.Auto,) * len(names))


def remove_host(mesh: Mesh, index: int, axis: str | None = None) -> Mesh:
    """Rebuild ``mesh`` without slice ``index`` along ``axis`` — the straggler
    eviction path.

    Surviving devices keep their relative order, so existing logical-axis
    sharding rules keep applying to the shrunk mesh; only the named axis loses
    one slice.  ``axis`` defaults to the mesh's first (host/data) axis.  A
    size-1 axis refuses the removal: a fleet cannot evict its last slice.
    """
    names = tuple(mesh.axis_names)
    axis = axis if axis is not None else names[0]
    if axis not in names:
        raise ValueError(f"mesh has no axis {axis!r}; axes are {names}")
    pos = names.index(axis)
    size = int(mesh.shape[axis])
    if size < 2:
        raise ValueError(
            f"cannot remove slice {index} from axis {axis!r} of size {size}: "
            f"a mesh cannot lose its last slice"
        )
    if not 0 <= index < size:
        raise ValueError(f"slice {index} out of range [0, {size}) on axis {axis!r}")
    devices = np.delete(np.asarray(mesh.devices), index, axis=pos)
    return Mesh(devices, names, axis_types=(AxisType.Auto,) * len(names))


def pipeline_submeshes(mesh: Mesh, axis: str) -> list[Mesh]:
    """One mesh per slice along ``axis``, spanning the remaining axes.

    The pipeline-stage hook: a launcher that pipelines over ``axis`` hands
    each stage its own submesh for stage-local work (per-stage data feeds,
    per-stage checkpoint shards, restaged parameter placement after a
    :class:`~repro.dist.pipeline.StagePlan` boundary move).  Slice ``i`` of
    the returned list holds the devices of pipeline rank ``i``; each submesh
    keeps the remaining axis names and device order, so existing sharding
    rules keep applying stage-locally.  A rank-1 mesh yields single-device
    ``(1,)`` submeshes (the axis name is retained with size 1).
    """
    names = tuple(mesh.axis_names)
    if axis not in names:
        raise ValueError(f"mesh has no axis {axis!r}; axes are {names}")
    pos = names.index(axis)
    devices = np.asarray(mesh.devices)
    out: list[Mesh] = []
    for i in range(int(mesh.shape[axis])):
        stage_devices = np.take(devices, [i], axis=pos)
        if len(names) > 1:
            stage_devices = np.squeeze(stage_devices, axis=pos)
            stage_names = names[:pos] + names[pos + 1:]
        else:
            stage_names = names
        out.append(Mesh(stage_devices, stage_names,
                        axis_types=(AxisType.Auto,) * len(stage_names)))
    return out
