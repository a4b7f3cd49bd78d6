"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax call, and smoke tests must keep seeing 1 device.

Axes:
  pod    inter-pod data parallelism / GP island axis (2 pods = 512 chips)
  data   intra-pod data parallelism + FSDP param sharding + GP data rows
  model  tensor/expert parallelism + GP population sharding
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over however many (fake) devices the host exposes —
    used by integration tests."""
    if pod > 1:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
