"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches JAX device state.  Single pod: 16x16 = 256 chips (v5e pod),
axes ("data", "model").  Multi-pod: 2x16x16 = 512 chips, axes
("pod", "data", "model") — "pod" is pure data parallelism over DCN/ICI.

Axes are ``Auto``: model code places activations with
``with_sharding_constraint`` (:func:`repro.dist.context.constrain`),
which ``jax.make_mesh``'s default ``Explicit`` axes reject.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the local device (CPU tests/examples)."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
