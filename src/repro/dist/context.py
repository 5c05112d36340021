"""Logical activation axes bound to concrete mesh axes per process.

Model code never names mesh axes directly: it constrains activations
against the *logical* axes ``"dp"`` (batch/data parallel — possibly a
tuple of mesh axes) and ``"tp"`` (tensor/model parallel), and the
launcher binds those for the span of a ``with`` block via
:func:`act_ctx`.  With no binding in place every :func:`constrain` is
the identity, so the same model code runs unsharded (CPU tests, the serving engine, eval
scripts) without carrying mesh plumbing.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "activation_axes",
    "mesh",
    "dp_size",
    "tp_size",
    "constrain",
    "act_ctx",
]

_state = threading.local()


def _get() -> dict[str, Any]:
    if not hasattr(_state, "v"):
        _state.v = {"dp": None, "tp": None, "mesh": None}
    return _state.v


def activation_axes() -> tuple[Any, Any]:
    s = _get()
    return s["dp"], s["tp"]


def mesh():
    return _get()["mesh"]


def _axis_size(m, ax) -> int:
    if m is None or ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= int(m.shape[a])
        return n
    return int(m.shape[ax])


def dp_size() -> int:
    s = _get()
    return _axis_size(s["mesh"], s["dp"])


def tp_size() -> int:
    s = _get()
    return _axis_size(s["mesh"], s["tp"])


def _resolve(entry):
    s = _get()
    if entry == "dp":
        return s["dp"]
    if entry == "tp":
        return s["tp"]
    return entry


def constrain(x, axes: Sequence[Any]):
    """``with_sharding_constraint`` against logical axes; identity when
    no mesh is bound (or every resolved entry is None)."""
    m = _get()["mesh"]
    if m is None:
        return x
    resolved = tuple(_resolve(e) for e in axes)
    if all(e is None for e in resolved):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, P(*resolved)))


@contextmanager
def act_ctx(*, dp=None, tp=None, mesh=None):
    """Bind the logical activation axes inside the ``with`` block and
    restore the previous binding on exit.

    ``dp`` may be a single mesh-axis name or a tuple of names (multi-pod
    data parallelism); ``tp`` is a single mesh-axis name.
    """
    s = _get()
    prev = (s["dp"], s["tp"], s["mesh"])
    s["dp"], s["tp"], s["mesh"] = dp, tp, mesh
    try:
        yield
    finally:
        s["dp"], s["tp"], s["mesh"] = prev
