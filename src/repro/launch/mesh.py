"""Production meshes.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_pipeline_mesh(*, n_stages: int = 4, multi_pod: bool = False):  # lint: allow-dead(pod mesh recipe for hillclimb/SPMD runs)
    """Courier pipeline mode: split the model axis into (stage, model).

    Same 256/512 chips, reshaped so the Pipeline Generator's stage
    boundaries map onto the ``stage`` axis (used by the hillclimb and the
    SPMD token-pipeline examples; the baseline dry-run uses
    :func:`make_production_mesh`).
    """
    tp = 16 // n_stages
    if n_stages * tp != 16:
        raise ValueError("n_stages must divide 16")
    if multi_pod:
        return _mesh((2, 16, n_stages, tp), ("pod", "data", "stage", "model"))
    return _mesh((16, n_stages, tp), ("data", "stage", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
