"""jit'd public wrappers over the Pallas kernels.

Handle model-level layouts (GQA head grouping, head_dim padding to the
128-lane MXU width) and select ``interpret=True`` automatically off-TPU
so the same call sites validate on CPU and run compiled on TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_bh
from .mamba_scan import mamba_scan_bd
from .flash_attention import flash_attention_bh
from .rmsnorm import rmsnorm_rows

__all__ = ["flash_attention", "decode_attention", "rmsnorm",
           "mamba_scan", "default_interpret"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_d(x, to: int = 128):
    D = x.shape[-1]
    if D % to == 0:
        return x, D
    pad = to - D % to
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]), D


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, S, H, D), k/v: (B, T, Hkv, D) -> (B, S, H, D)."""
    interpret = default_interpret() if interpret is None else interpret
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / math.sqrt(D)
    # repeat kv heads to match q heads, flatten (B, H)
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    qf, D0 = _pad_d(qf)
    kf, _ = _pad_d(kf)
    vf, _ = _pad_d(vf)
    out = flash_attention_bh(qf, kf, vf, scale=scale, causal=causal,
                             window=window, interpret=interpret)
    out = out[..., :D0]
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, lengths, *,
                     interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, H, D), k/v: (B, T, Hkv, D), lengths: (B,) -> (B, H, D)."""
    interpret = default_interpret() if interpret is None else interpret
    B, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / math.sqrt(D)
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qf = q.reshape(B * H, 1, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    qf, D0 = _pad_d(qf)
    kf, _ = _pad_d(kf)
    vf, _ = _pad_d(vf)
    lens = jnp.repeat(lengths, H)
    out = decode_attention_bh(qf, kf, vf, lens, scale=scale,
                              interpret=interpret)
    return out[..., :D0].reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6,
            interpret: bool | None = None) -> jnp.ndarray:
    """x: (..., D), scale: (D,)."""
    interpret = default_interpret() if interpret is None else interpret
    shape = x.shape
    rows = x.reshape(-1, shape[-1])
    out = rmsnorm_rows(rows, scale, eps=eps, interpret=interpret)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_scan(x, dt, bm, cm, a, d_skip, *,
               interpret: bool | None = None) -> jnp.ndarray:
    """Selective scan: x/dt (B,T,Dc), bm/cm (B,T,S), a (Dc,S), d (Dc,)."""
    interpret = default_interpret() if interpret is None else interpret
    return mamba_scan_bd(x, dt, bm, cm, a, d_skip, interpret=interpret)
