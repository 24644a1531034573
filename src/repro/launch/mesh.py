"""Production mesh definitions.

Functions, not module-level constants: importing this module never touches
jax device state. The dry-run sets XLA_FLAGS before any jax import to get
512 host platform devices.

Mesh semantics (DESIGN.md §2):
  * "model" — tensor parallelism inside one federated client (16 chips);
  * "data"  — the FL client axis: one slice per client;
  * "pod"   — second pod; in the federated regime pod×data = 32 clients,
    and the user-centric mixing collective crosses the pod boundary (DCI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh for CPU smoke tests (uses however many devices exist)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(min(model, n // data), 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def client_axes(mesh) -> tuple:
    """Mesh axes that enumerate federated clients."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_clients(mesh) -> int:
    total = 1
    for a in client_axes(mesh):
        total *= mesh.shape[a]
    return total


def num_chips(mesh) -> int:
    total = 1
    for a in mesh.axis_names:
        total *= mesh.shape[a]
    return total
