"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state: tests and benches must keep seeing the
devices their process was started with.

Topology assumption (TPU v5e-style): 16x16 = 256 chips per pod, 2 pods via
DCN.  Axis roles: ``model`` = fast ICI ring (TP/EP), ``data`` = second ICI
dim (DP + FSDP), ``pod`` = DCN (pure DP).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for in-process sharding tests (requires >= n_data*n_model
    visible devices, e.g. via xla_force_host_platform_device_count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def describe(mesh) -> dict:
    return dict(
        shape=dict(zip(mesh.axis_names, mesh.devices.shape)),
        n_devices=int(mesh.devices.size),
        axis_names=list(mesh.axis_names),
    )
