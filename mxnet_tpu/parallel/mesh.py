"""Device-mesh construction helpers.

The reference's multi-device story is per-GPU worker threads + kvstore
reduce (SURVEY §2.4); the TPU-native story is one ``jax.sharding.Mesh``
whose axes name the parallelism kinds.  Convention here:

* ``data``  — data parallelism (batch dim sharded; grad psum rides ICI)
* ``model`` — tensor parallelism (weight dims sharded; GSPMD inserts
  all-gather/reduce-scatter)

Pipeline/sequence/expert axes are added by their owners when used.
"""
from __future__ import annotations

import numpy as np

from ..telemetry.spans import span

__all__ = ["build_mesh", "build_mesh_from_axes", "data_parallel_spec",
           "largest_tp_factor"]


def largest_tp_factor(n, cap=8):
    """Largest power-of-two divisor of n, capped (heuristic tp size)."""
    tp = 1
    while n % (tp * 2) == 0 and tp * 2 <= cap:
        tp *= 2
    return tp


def build_mesh(n_devices=None, tp=1, pp=1, axis_names=None,
               devices=None):
    """Build a Mesh over the first n_devices jax devices.

    tp > 1 -> ('data', 'model') axes (tensor parallel inner);
    pp > 1 -> ('data', 'pipe') axes (pipeline stages inner; tp must be
    1 — packed pipeline stage params cannot also be tensor-sharded).
    """
    # where ``devices`` is None the span holds ``jax.devices()``: in a
    # process that has touched no device yet, the runtime's bring-up
    with span("mesh.build", category="mesh") as sp:
        mesh = _build_mesh(n_devices, tp, pp, axis_names, devices)
        sp.attrs = {"devices": int(mesh.devices.size),
                    "axes": dict(mesh.shape)}
    return mesh


def _build_mesh(n_devices, tp, pp, axis_names, devices):
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    n = len(devices)
    if pp > 1:
        assert tp == 1, "tp and pp cannot both exceed 1 in build_mesh"
        assert n % pp == 0, "n_devices %d not divisible by pp %d" % (n, pp)
        arr = np.array(devices).reshape(n // pp, pp)
        return Mesh(arr, axis_names=axis_names or ("data", "pipe"))
    axis_names = axis_names or ("data", "model")
    assert n % tp == 0, "n_devices %d not divisible by tp %d" % (n, tp)
    if len(axis_names) == 1:
        assert tp == 1, "single-axis mesh cannot have tp > 1"
        arr = np.array(devices)
    else:
        arr = np.array(devices).reshape(n // tp, tp)
    return Mesh(arr, axis_names=axis_names)


def build_mesh_from_axes(axes, devices=None):
    """Mesh matching a reshard mesh-descriptor's axes dict, e.g.
    ``{"data": 4, "model": 2}`` (``parallel/reshard.py``;
    ``tools/reshard.py --mesh data=4,model=2`` parses into this form).
    Axis order follows the dict's insertion order; an empty dict gives
    a single-device ``('data',)`` mesh.  Raises ValueError when the
    product exceeds the available devices."""
    import jax
    from jax.sharding import Mesh
    axes = {str(k): int(v) for k, v in (axes or {}).items()} \
        or {"data": 1}
    n = 1
    for v in axes.values():
        n *= v
    devs = list(devices if devices is not None else jax.devices())
    if n > len(devs):
        raise ValueError(
            "mesh axes %r need %d devices, have %d" % (axes, n, len(devs)))
    arr = np.array(devs[:n]).reshape(tuple(axes.values()))
    return Mesh(arr, axis_names=tuple(axes))


# trace-time routing for the Pallas kernels (ops/fused.py,
# ops/pallas_kernels.py): GSPMD cannot partition a Mosaic kernel, so
# under a mesh of more than one device each kernel call site wraps
# itself in a shard_map over this mesh
_KERNEL_MESH = None


class kernel_mesh:
    """Context manager naming the mesh that kernels traced within are
    partitioned over (ShardedTrainer's step and forward traces set it);
    a one-device mesh or ``None`` deactivates."""

    def __init__(self, mesh):
        self.mesh = mesh if mesh is not None and mesh.devices.size > 1 \
            else None

    def __enter__(self):
        global _KERNEL_MESH
        self._prev = _KERNEL_MESH
        _KERNEL_MESH = self.mesh
        return self

    def __exit__(self, *exc):
        global _KERNEL_MESH
        _KERNEL_MESH = self._prev


def active_kernel_mesh():
    return _KERNEL_MESH


def kernel_axes(mesh, rows, cols):
    """(row_axis, col_axis) a kernel under ``mesh`` splits ``rows``
    (batch-major, over the first mesh axis) and ``cols`` (output
    channels or heads, over 'model') along; None where the mesh has no
    such axis or the size does not divide."""
    row_axis = mesh.axis_names[0]
    if rows % mesh.shape[row_axis]:
        row_axis = None
    col_axis = "model" if "model" in mesh.axis_names else None
    if col_axis is not None and cols % mesh.shape[col_axis]:
        col_axis = None
    return row_axis, col_axis


def shard_map_nocheck(f, mesh, in_specs, out_specs):
    """shard_map with replication checking off."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def data_parallel_spec(mesh):
    """PartitionSpec sharding dim 0 (batch) over the data axis."""
    from jax.sharding import PartitionSpec as P
    return P(mesh.axis_names[0])


def make_1d_mesh(axis_name, n_devices, devices=None):
    """1-D mesh with ``axis_name`` over exactly ``n_devices`` devices."""
    import jax
    import numpy as _np
    devs = list(devices if devices is not None else jax.devices())[:n_devices]
    if len(devs) < n_devices:
        raise ValueError("need %d devices for the %r axis, have %d"
                         % (n_devices, axis_name, len(devs)))
    return jax.sharding.Mesh(_np.array(devs), (axis_name,))
