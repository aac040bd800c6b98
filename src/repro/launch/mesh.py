"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.

Mesh shapes (assignment):
  single-pod:  (16, 16)      = ("data", "model")   — 256 chips
  multi-pod:   (2, 16, 16)   = ("pod", "data", "model") — 512 chips
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))


def make_lane_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """1-D mesh over local devices for lane-parallel sweeps (axis "lanes").

    The sweep runtime (repro.runtime.sweep) shard_maps the lane axis of a
    (policy × seed × config) sweep over this mesh; lanes are embarrassingly
    parallel, so the mesh carries no collectives.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    return make_mesh((n_devices,), ("lanes",))


def make_vertices_mesh(n_devices: int | None = None,
                       devices=None) -> jax.sharding.Mesh:
    """1-D mesh over local devices for vertex-sharded sessions (axis
    "vertices").

    One session's per-vertex state (adjacency rows, label journal,
    presence/touch counters) is laid out as per-device row blocks along
    this axis; the K-sized loads and the O(K²) cut matrix stay replicated
    and are combined with ``lax.psum`` once per window
    (repro.runtime.shard_session).

    ``devices`` selects an explicit device subset (benchmarks sweep mesh
    widths this way — the device count cannot change in-process);
    otherwise the first ``n_devices`` local devices are used.
    """
    import numpy as np
    if devices is None:
        avail = jax.devices()
        if n_devices is None:
            n_devices = len(avail)
        if n_devices > len(avail):
            raise ValueError(
                f"make_vertices_mesh(n_devices={n_devices}) exceeds the "
                f"{len(avail)} local devices — force more with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N or "
                "pass an explicit devices= subset")
        devices = avail[:n_devices]
    return jax.sharding.Mesh(np.asarray(devices), ("vertices",))


def make_grid_mesh(n_lanes: int, n_vertices: int,
                   devices=None) -> jax.sharding.Mesh:
    """2-D (lanes × vertices) mesh: sweep lanes on the first axis, each
    lane's vertex blocks on the second.

    This is the composition guard for the two 1-D meshes: asking for
    ``make_lane_mesh()`` (which claims every local device) *and* a
    vertices mesh used to silently oversubscribe the device pool. Build
    the grid explicitly instead; the product must fit the device budget
    or this raises with the arithmetic spelled out.
    """
    import numpy as np
    if n_lanes < 1 or n_vertices < 1:
        raise ValueError(
            f"make_grid_mesh(n_lanes={n_lanes}, n_vertices={n_vertices}): "
            "both axis sizes must be >= 1")
    if devices is None:
        devices = jax.devices()
    need = n_lanes * n_vertices
    if need > len(devices):
        raise ValueError(
            f"make_grid_mesh(n_lanes={n_lanes}, n_vertices={n_vertices}) "
            f"needs {need} devices but only {len(devices)} are available — "
            "shrink one axis, force more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N, or run "
            "lane-sharded and vertex-sharded work as separate sweeps")
    grid = np.asarray(devices[:need]).reshape(n_lanes, n_vertices)
    return jax.sharding.Mesh(grid, ("lanes", "vertices"))


def mesh_devices(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
