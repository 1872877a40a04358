"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see the
real single-device CPU).
"""
from __future__ import annotations

import jax


def _make(shape, axes):
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods over DCN for the multi-pod run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh_like(shape: tuple[int, ...]):
    """Arbitrary dev-count meshes for tests/examples (e.g. (2,2,2) on 8
    host devices)."""
    axes = ("pod", "data", "model")[-len(shape):]
    return _make(shape, axes)


def make_pod_mesh(n_pods: int):
    """1-D pod-only mesh: every member is one pod gateway.  Used by the
    train driver's --grad-sync seqbalance mode, where the whole grad sync
    runs over the pod axis through dist.collectives."""
    return _make((n_pods,), ("pod",))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
