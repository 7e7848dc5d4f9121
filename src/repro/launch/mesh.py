"""Production mesh construction (TPU v5e target).

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model").

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests must keep
seeing the single real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh over the real local device (CPU smoke paths)."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes a batch-like dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def client_axes(mesh) -> tuple:
    """Mesh axes the federated CLIENT axis shards over (major to minor).

    Same axes a batch dimension uses — one sampled client per data-group —
    but returned only for axes present on the mesh, in the fixed
    ``("pod", "data")`` order the engine's positional client split relies
    on (shard position = row-major index over these axes).
    """
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_engine_mesh(n_client_shards: int = None):
    """Mesh for the client-parallel engine on the locally visible devices.

    Factors ``n_client_shards`` devices (default: all of them) into
    ``(data, model=1)`` — the engine shards the client axis over ``data``
    and treats ``model`` as replicated.  Raising the device count is done
    by the launcher (``XLA_FLAGS=--xla_force_host_platform_device_count``
    for CPU simulation), never here.
    """
    n = n_client_shards or len(jax.devices())
    if n > len(jax.devices()):
        raise ValueError(f"engine mesh wants {n} devices, only "
                         f"{len(jax.devices())} visible")
    return make_mesh((n, 1), ("data", "model"))


def axis_size(mesh, *names) -> int:
    s = 1
    for n in names:
        if n in mesh.axis_names:
            s *= mesh.shape[n]
    return s
