"""Pallas TPU decode-attention (flash-decode) kernel.

One new query token per (batch, head) against a long KV cache:
q: (BH, 1, D), k/v: (BH, T, D), valid length per row: (BH,).

Grid: ``(BH, T // block_k)`` — the KV axis is the *sequential* grid
dimension (TPU executes the last grid axis in order), so partial
(m, l, acc) online-softmax statistics accumulate in VMEM scratch and
are finalised by the last program.  Long caches therefore stream
through VMEM in ``block_k`` tiles; this is the kernel shape that makes
the ``long_500k`` cells viable on the sequence-sharded cache layout.

The per-row lengths are scalar-prefetched into SMEM (a (BH, 1) VMEM
block would break the TPU's (8, 128) tiling rule), and the running
max / sum live in lane-wide ``(1, 128)`` scratch rows whose lanes all
hold the same value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_bh"]

_NEG_INF = -1e30
_LANES = 128


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_k: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale            # (1, D)
    k = k_ref[...].astype(jnp.float32)                    # (bk, D)
    v = v_ref[...].astype(jnp.float32)
    valid_len = len_ref[bh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bk)
    idx = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    s = jnp.where(idx < valid_len, s, _NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]               # (1, 128) each
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...][:, :1], 1e-30)
                      ).astype(o_ref.dtype)


def decode_attention_bh(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        lengths: jnp.ndarray, *, scale: float,
                        block_k: int = 512,
                        interpret: bool = False) -> jnp.ndarray:
    """q: (BH, 1, D), k/v: (BH, T, D), lengths: (BH,) -> (BH, 1, D)."""
    BH, _, D = q.shape
    T = k.shape[1]
    block_k = min(block_k, T)
    assert T % block_k == 0
    kernel = functools.partial(_kernel, scale=scale, block_k=block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, T // block_k),
        in_specs=[
            pl.BlockSpec((None, 1, D), lambda bh, ki, lens: (bh, 0, 0)),
            pl.BlockSpec((None, block_k, D),
                         lambda bh, ki, lens: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, D),
                         lambda bh, ki, lens: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, D), lambda bh, ki, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, _LANES), jnp.float32),   # m
            pltpu.VMEM((1, _LANES), jnp.float32),   # l
            pltpu.VMEM((1, D), jnp.float32),        # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
