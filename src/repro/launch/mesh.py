"""Production meshes. v5e pod = 16x16 = 256 chips; multi-pod adds a leading
'pod' axis (2 pods = 512 chips over DCN).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (~intra-pod)
DCN_BW = 6.25e9                   # bytes/s per host (~inter-pod, 50 Gbps)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CI-scale distribution tests (needs >= data*model
    host-platform devices)."""
    return jax.make_mesh((data, model), ("data", "model"))


def make_debug_cluster_mesh():
    """1-D 'data' mesh over every host-platform device — the CI-scale
    clustering mesh (set XLA_FLAGS=--xla_force_host_platform_device_count=4
    in the environment before the first jax call)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",))


def auto_axes(mesh):
    """``mesh`` over the same devices with every axis Auto-typed.

    ``jax.make_mesh`` types its axes Explicit, under which a gather of a
    sharded array outside ``shard_map`` must name its output sharding; the
    sharded fits here leave sharding outside ``shard_map`` to the
    compiler."""
    auto = (jax.sharding.AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) == auto:
        return mesh
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh ('pod' included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s
