"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization.  Every mesh is built with ``Auto`` axis types.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods x 256
    chips (pod, data, model) — 'pod' is the outer data-parallel axis (and can
    be re-bound to pipeline stages, see training/pipeline.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (host) devices exist — used by tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
