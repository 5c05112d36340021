"""Attention layers: GQA (with optional QKV bias / sliding window) and
MLA (DeepSeek-V2 multi-head latent attention with compressed KV cache).

Every variant exposes:

* ``init(key, cfg) -> params``
* ``fwd(params, cfg, x, cos, sin) -> y``                (full-sequence)
* ``init_cache(cfg, batch, max_len, dtype) -> cache``
* ``decode(params, cfg, x, cache, pos) -> (y, rows)``   (one new token)

``decode`` reads its cache and never writes it: ``rows`` holds, for
each cache leaf, the new token's row ``(B, 1, ...)`` in the cache's
dtype, which the caller writes at ``pos % slots`` on the position axis
(``repro.models.transformer.decode_step``).  Attention reads the old
cache with that row selected in at its slot, the same values an
updated cache would hold.

The scaled-dot-product core is pluggable (``impl='xla' | 'pallas'``) so
the Pallas TPU kernels in :mod:`repro.kernels` can be swapped in on
real hardware while the dry-run lowers the pure-XLA path.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .common import (ModelConfig, PyTree, apply_rope, dense, make_dense,
                     yarn_mscale)

__all__ = ["GQA", "MLA", "sdpa", "decode_sdpa", "causal_mask_bias"]

_NEG_INF = -1e30


def _with_row(cache: jnp.ndarray, row: jnp.ndarray,
              slot: jnp.ndarray) -> jnp.ndarray:
    """``cache`` (B, T, ...) as it reads with ``row`` (B, 1, ...)
    written at ``slot``: a select the scores' reads fuse, so the
    cache is not copied to update one row."""
    idx = jnp.arange(cache.shape[1]).reshape((1, -1) + (1,) * (cache.ndim - 2))
    return jnp.where(idx == slot, row, cache)


# ---------------------------------------------------------------------------
# Scaled-dot-product cores
# ---------------------------------------------------------------------------

def causal_mask_bias(q_len: int, kv_len: int, *, causal: bool,
                     window: int | None, q_offset: int = 0) -> jnp.ndarray:
    """(q_len, kv_len) additive bias implementing causal + sliding window."""
    qi = jnp.arange(q_len)[:, None] + q_offset
    ki = jnp.arange(kv_len)[None, :]
    ok = jnp.ones((q_len, kv_len), bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         bias: jnp.ndarray | None, *, scale: float) -> jnp.ndarray:
    """Reference scaled-dot-product attention with GQA head grouping.

    q: (B, S, H, Dk)   k: (B, T, Hkv, Dk)   v: (B, T, Hkv, Dv)
    bias: (S, T) additive or None.  Softmax in f32.
    """
    B, S, H, Dk = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, Dk)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", w.astype(v.dtype), v)
    return out.reshape(B, S, H, v.shape[-1])


def blockwise_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   scale: float, causal: bool, window: int | None,
                   q_chunk: int = 1024, kv_chunk: int = 1024
                   ) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure XLA.

    Peak memory is one (q_chunk, kv_chunk) score tile per head group —
    this is the XLA twin of the Pallas kernel in ``repro.kernels`` and
    the path the dry-run lowers.  For sliding-window attention each
    query chunk only visits a fixed-width KV span (window + q_chunk),
    so SWA prefill stays O(S * window).
    """
    from repro.dist.context import constrain
    B, S, H, Dk = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    while S % q_chunk:
        q_chunk //= 2
    while T % kv_chunk:
        kv_chunk //= 2
    nq = S // q_chunk

    # SPMD propagation through while loops is weak: pin batch (dp) and
    # head (tp) sharding of the loop-invariant operands and every block
    # slice, or the backward replicates (B, S, H, D) cotangents on
    # every device.  Attention is embarrassingly parallel over heads;
    # the tp axis is dropped automatically when it doesn't divide.
    q = constrain(q, ("dp", None, "tp", None))
    k = constrain(k, ("dp", None, "tp", None))
    v = constrain(v, ("dp", None, "tp", None))

    span = None
    if window is not None and causal:
        span = min(T, -(-(window + q_chunk) // kv_chunk) * kv_chunk)

    def q_block(_, qi):
        q_off = qi * q_chunk
        qb = jax.lax.dynamic_slice_in_dim(q, q_off, q_chunk, axis=1)
        qb = constrain(qb, ("dp", None, "tp", None))
        qb = qb.reshape(B, q_chunk, Hkv, g, Dk)
        qb = constrain(qb, ("dp", None, "tp", None, None))

        if span is not None:
            kv_start = jnp.clip(q_off + q_chunk - span, 0, T - span)
            kb_all = jax.lax.dynamic_slice_in_dim(k, kv_start, span, axis=1)
            vb_all = jax.lax.dynamic_slice_in_dim(v, kv_start, span, axis=1)
            nkv = span // kv_chunk
        else:
            kv_start = jnp.int32(0)
            kb_all, vb_all = k, v
            nkv = T // kv_chunk

        def kv_block(carry, ki):
            m_acc, l_acc, o_acc = carry
            kv_off = ki * kv_chunk
            kb = jax.lax.dynamic_slice_in_dim(kb_all, kv_off, kv_chunk,
                                              axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vb_all, kv_off, kv_chunk,
                                              axis=1)
            s_ = jnp.einsum("bshgd,bthd->bhgst", qb, kb,
                            preferred_element_type=jnp.float32) * scale
            qi_idx = q_off + jnp.arange(q_chunk)[:, None]
            ki_idx = kv_start + kv_off + jnp.arange(kv_chunk)[None, :]
            ok = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                ok &= ki_idx <= qi_idx
            if window is not None:
                ok &= ki_idx > qi_idx - window
            s_ = jnp.where(ok, s_, _NEG_INF)
            s_ = constrain(s_, ("dp", "tp", None, None, None))
            m_new = jnp.maximum(m_acc, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m_new[..., None])
            corr = jnp.exp(m_acc - m_new)
            l_new = l_acc * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgst,bthd->bhgsd", p.astype(vb.dtype), vb)
            o_new = o_acc * corr[..., None].astype(o_acc.dtype) + pv
            o_new = constrain(o_new, ("dp", "tp", None, None, None))
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hkv, g, q_chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, Hkv, g, q_chunk, v.shape[-1]), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(kv_block), (m0, l0, o0), jnp.arange(nkv))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        # (B,Hkv,g,qc,Dv) -> (B,qc,H,Dv)
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, q_chunk, H, v.shape[-1])
        o = constrain(o, ("dp", None, "tp", None))
        return None, o.astype(v.dtype)

    _, blocks = jax.lax.scan(q_block, None, jnp.arange(nq))
    blocks = constrain(blocks, (None, "dp", None, "tp", None))
    # (nq, B, q_chunk, H, Dv) -> (B, S, H, Dv)
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])
    return constrain(out, ("dp", None, "tp", None))


def decode_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                length_mask: jnp.ndarray, *, scale: float) -> jnp.ndarray:
    """Single-position attention against a (possibly oversized) cache.

    q: (B, H, Dk)  k/v: (B, T, Hkv, D*)  length_mask: (B, T) bool valid.
    """
    B, H, Dk = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Dk)
    logits = jnp.einsum("bhgd,bthd->bhgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(length_mask[:, None, None, :], logits, _NEG_INF)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", w.astype(v.dtype), v)
    return out.reshape(B, H, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

class GQA:
    """Grouped-query attention with RoPE, bias and sliding-window options."""

    @staticmethod
    def init(key, cfg: ModelConfig) -> PyTree:
        d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        ks = jax.random.split(key, 4)
        b = cfg.qkv_bias
        return {
            "wq": make_dense(ks[0], d, H * hd, bias=b),
            "wk": make_dense(ks[1], d, Hkv * hd, bias=b),
            "wv": make_dense(ks[2], d, Hkv * hd, bias=b),
            "wo": make_dense(ks[3], H * hd, d,
                             scale=1.0 / math.sqrt(H * hd * 2 * cfg.n_layers)),
        }

    @staticmethod
    def _qkv(p, cfg, x):
        B, S, _ = x.shape
        q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, cfg.head_dim)
        k = dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        v = dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        return q, k, v

    @staticmethod
    def fwd(p: PyTree, cfg: ModelConfig, x: jnp.ndarray,
            cos: jnp.ndarray, sin: jnp.ndarray, *,
            impl: str = "xla") -> jnp.ndarray:
        B, S, _ = x.shape
        q, k, v = GQA._qkv(p, cfg, x)
        if cfg.use_rope:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        if impl == "pallas":  # pragma: no cover - TPU path
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=cfg.causal,
                                       window=cfg.sliding_window)
        elif S > 2048:
            out = blockwise_sdpa(q, k, v, scale=scale, causal=cfg.causal,
                                 window=cfg.sliding_window)
        else:
            bias = causal_mask_bias(S, S, causal=cfg.causal,
                                    window=cfg.sliding_window)
            out = sdpa(q, k, v, bias, scale=scale)
        return dense(p["wo"], out.reshape(B, S, -1))

    # -- decode -------------------------------------------------------
    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> PyTree:
        # Sliding-window models only ever need `window` cache slots
        # (ring buffer); full attention needs max_len.
        slots = min(max_len, cfg.sliding_window or max_len)
        shape = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    @staticmethod
    def decode(p: PyTree, cfg: ModelConfig, x: jnp.ndarray, cache: PyTree,
               pos: jnp.ndarray) -> tuple[jnp.ndarray, PyTree]:
        """x: (B, 1, d); pos: scalar int32 (tokens already in cache)."""
        from .common import rope_tables
        B = x.shape[0]
        q, k, v = GQA._qkv(p, cfg, x)
        if cfg.use_rope:
            cos, sin = rope_tables(pos[None], cfg.head_dim, cfg.rope_theta,
                                   cfg.rope_scaling)
            q = apply_rope(q, cos[None], sin[None])
            k = apply_rope(k, cos[None], sin[None])
        slots = cache["k"].shape[1]
        slot = pos % slots
        k = k.astype(cache["k"].dtype)
        v = v.astype(cache["v"].dtype)
        ck = _with_row(cache["k"], k, slot)
        cv = _with_row(cache["v"], v, slot)
        idx = jnp.arange(slots)
        if cfg.sliding_window is not None and slots == cfg.sliding_window:
            valid = (idx <= slot) | (pos >= slots)  # ring buffer fully warm
            valid = valid & (idx < jnp.minimum(pos + 1, slots))
            valid = jnp.broadcast_to(valid, (B, slots))
        else:
            valid = jnp.broadcast_to(idx <= pos, (B, slots))
        scale = 1.0 / math.sqrt(cfg.head_dim)
        out = decode_sdpa(q[:, 0], ck, cv, valid, scale=scale)
        y = dense(p["wo"], out.reshape(B, 1, -1).astype(x.dtype))
        return y, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

class MLA:
    """Multi-head latent attention with low-rank compressed KV cache.

    Cache stores only ``c_kv`` (kv_lora_rank) and the shared rope key
    (qk_rope_head_dim) per token.  Decode uses the *absorbed* form so
    the compressed cache is attended to directly.

    The rope channels of q and k are rotated in halves (pair ``i`` is
    channels ``i`` and ``i + dr/2``); the published checkpoints pair
    adjacent channels, which is the same model with those projection
    columns permuted.  Under YaRN ``rope_scaling`` the softmax scale
    is ``mscale(factor, mscale_all_dim)**2 / sqrt(dn + dr)``.
    """

    @staticmethod
    def softmax_scale(cfg: ModelConfig) -> float:
        scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        s = dict(cfg.rope_scaling or ())
        if s.get("mscale_all_dim"):
            scale *= yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2
        return scale

    @staticmethod
    def init(key, cfg: ModelConfig) -> PyTree:
        d, H = cfg.d_model, cfg.n_heads
        r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        ks = iter(jax.random.split(key, 10))
        p = {
            "w_dkv": make_dense(next(ks), d, r_kv),
            "w_krope": make_dense(next(ks), d, dr),
            "w_uk": make_dense(next(ks), r_kv, H * dn),
            "w_uv": make_dense(next(ks), r_kv, H * dv),
            "wo": make_dense(next(ks), H * dv, d,
                             scale=1.0 / math.sqrt(H * dv * 2 * cfg.n_layers)),
            "kv_norm": {"scale": jnp.ones((r_kv,), jnp.float32)},
        }
        if r_q:
            p["w_dq"] = make_dense(next(ks), d, r_q)
            p["w_uq"] = make_dense(next(ks), r_q, H * (dn + dr))
            p["q_norm"] = {"scale": jnp.ones((r_q,), jnp.float32)}
        else:
            p["wq"] = make_dense(next(ks), d, H * (dn + dr))
        return p

    @staticmethod
    def _q(p, cfg, x):
        from .common import rmsnorm
        B, S, _ = x.shape
        H = cfg.n_heads
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        if "w_dq" in p:
            q = dense(p["w_uq"], rmsnorm(p["q_norm"], dense(p["w_dq"], x)))
        else:
            q = dense(p["wq"], x)
        q = q.reshape(B, S, H, dn + dr)
        return q[..., :dn], q[..., dn:]

    @staticmethod
    def _ckv(p, cfg, x):
        from .common import rmsnorm
        c_kv = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x))
        k_rope = dense(p["w_krope"], x)  # (B, S, dr) shared across heads
        return c_kv, k_rope

    @staticmethod
    def fwd(p: PyTree, cfg: ModelConfig, x: jnp.ndarray,
            cos: jnp.ndarray, sin: jnp.ndarray, *,
            impl: str = "xla") -> jnp.ndarray:
        B, S, _ = x.shape
        H = cfg.n_heads
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        q_nope, q_rope = MLA._q(p, cfg, x)
        c_kv, k_rope = MLA._ckv(p, cfg, x)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # (B,S,1,dr)
        k_nope = dense(p["w_uk"], c_kv).reshape(B, S, H, dn)
        v = dense(p["w_uv"], c_kv).reshape(B, S, H, dv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        scale = MLA.softmax_scale(cfg)
        if S > 2048:
            out = blockwise_sdpa(q, k, v, scale=scale, causal=True,
                                 window=None)
        else:
            bias = causal_mask_bias(S, S, causal=True, window=None)
            out = sdpa(q, k, v, bias, scale=scale)
        return dense(p["wo"], out.reshape(B, S, -1))

    # -- decode (absorbed form) ---------------------------------------
    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> PyTree:
        return {
            "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        }

    @staticmethod
    def decode(p: PyTree, cfg: ModelConfig, x: jnp.ndarray, cache: PyTree,
               pos: jnp.ndarray) -> tuple[jnp.ndarray, PyTree]:
        from .common import rope_tables
        B = x.shape[0]
        H = cfg.n_heads
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        r_kv = cfg.kv_lora_rank
        q_nope, q_rope = MLA._q(p, cfg, x)          # (B,1,H,dn),(B,1,H,dr)
        c_kv, k_rope = MLA._ckv(p, cfg, x)          # (B,1,r_kv),(B,1,dr)
        cos, sin = rope_tables(pos[None], dr, cfg.rope_theta,
                               cfg.rope_scaling)
        q_rope = apply_rope(q_rope, cos[None], sin[None])
        k_rope = apply_rope(k_rope[:, :, None, :], cos[None], sin[None])[:, :, 0]
        c_kv = c_kv.astype(cache["c_kv"].dtype)
        k_rope = k_rope.astype(cache["k_rope"].dtype)
        T = cache["c_kv"].shape[1]
        ck = _with_row(cache["c_kv"], c_kv, pos % T)
        cr = _with_row(cache["k_rope"], k_rope, pos % T)
        valid = jnp.broadcast_to(jnp.arange(T) <= pos, (B, T))
        # Absorb W_uk into the query: q_c = q_nope @ W_uk^T  (per head).
        with jax.named_scope("mla.absorb_q"):
            w_uk = p["w_uk"]["w"].reshape(r_kv, H, dn)
            q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0],
                             w_uk.astype(q_nope.dtype))    # (B,H,r_kv)
        with jax.named_scope("mla.latent_scores"):
            logits = jnp.einsum("bhr,btr->bht", q_c, ck,
                                preferred_element_type=jnp.float32)
            logits = logits + jnp.einsum(
                "bhd,btd->bht", q_rope[:, 0], cr,
                preferred_element_type=jnp.float32)
            logits = logits * MLA.softmax_scale(cfg)
            logits = jnp.where(valid[:, None, :], logits, _NEG_INF)
            w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        # Absorb W_uv on the way out.
        with jax.named_scope("mla.absorb_v"):
            ctx = jnp.einsum("bht,btr->bhr", w.astype(ck.dtype), ck)
            w_uv = p["w_uv"]["w"].reshape(r_kv, H, dv)
            out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(ctx.dtype))
        y = dense(p["wo"], out.reshape(B, 1, -1).astype(x.dtype))
        return y, {"c_kv": c_kv, "k_rope": k_rope}
