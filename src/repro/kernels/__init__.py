"""Pallas TPU kernels (validated in interpret mode on CPU).

Each kernel ships three pieces: the ``pl.pallas_call`` implementation
with explicit BlockSpec VMEM tiling, a pure-jnp oracle in ``ref.py``,
and a jit'd public wrapper in ``ops.py``.
"""

from . import ops, ref

__all__ = ["ops", "ref"]
