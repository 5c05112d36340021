"""Plain float32 decoder pieces shared by the reference families.

Written from the published model descriptions (the Hugging Face
``modeling_qwen2`` / ``modeling_mixtral`` equations), in plain
``jax.numpy`` at ``Precision.HIGHEST``, with no cache, no batching and
no kernel: one sequence, every position at once, causal. Imports
nothing of the program.

Weights come as a plain dict (see ``chipbench/families/common.py``):
``x @ W`` orientation, the layers stacked on a leading axis. They are
read in whatever dtype they are stored in and computed in float32.

``control=True`` computes the same mathematics with every matrix
weight rounded to float8 (e4m3) with one scale per output channel: the
precision one step below the configuration's bf16, the step a faster
path would be tempted to take. It is the control that the correctness
comparison has to fail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts

__all__ = ["HI", "mm", "rmsnorm", "rope", "attention", "swiglu", "weight",
           "decoder_layer", "Reference", "gqa_work"]

HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def mm(a, b):
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def weight(w, control: bool, axis: int = -2):
    """``w`` in float32; under ``control`` rounded through float8 with
    one scale per slice along ``axis`` (the input axis: one scale per
    output channel)."""
    w = w.astype(jnp.float32)
    if not control:
        return w
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(_F8).astype(jnp.float32) * s


def rmsnorm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def rope(x, pos, theta: float):
    """Rotary embedding, rotate-half form. x: (P, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def attention(cfg: dict, lw: dict, x, control: bool):
    """Causal multi-head attention with grouped k/v heads, rotary
    positions, optional q/k/v bias and optional sliding window."""
    P, d = x.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    q, k, v = (mm(x, weight(lw[n], control)) for n in ("wq", "wk", "wv"))
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    pos = jnp.arange(P)
    q = rope(q.reshape(P, H, hd), pos, cfg["rope_theta"])
    k = rope(k.reshape(P, Hkv, hd), pos, cfg["rope_theta"])
    v = v.reshape(P, Hkv, hd)
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    qi, ki = pos[:, None], pos[None, :]
    ok = ki <= qi
    window = cfg.get("sliding_window")
    if window is not None and cfg.get("use_sliding_window", True):
        ok &= qi - ki < window
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(P, H * hd)
    return mm(o, weight(lw["wo"], control))


def swiglu(x, wg, wu, wd, control: bool):
    h = jax.nn.silu(mm(x, weight(wg, control))) * mm(x, weight(wu, control))
    return mm(h, weight(wd, control))


def decoder_layer(cfg: dict, ffn, attn=attention):
    """One pre-norm decoder layer, ``layer(lw, x, control)``: ``attn``
    on the normed residual stream, then the family's feed-forward block
    ``ffn(cfg, lw, h, control)`` on the normed stream, each added to
    it."""
    eps = float(cfg["rms_norm_eps"])

    def layer(lw, x, control):
        h = rmsnorm(x, lw["attn_norm"], eps)
        x = x + attn(cfg, lw, h, control)
        h = rmsnorm(x, lw["mlp_norm"], eps)
        return x + ffn(cfg, lw, h, control)
    return layer


class Reference:
    """Full-sequence logits of one family, computed layer by layer.

    ``stacks`` lists the decoder's layers in order as ``(key, layer)``:
    the weights of one stack's layers lie under ``weights[key]`` on a
    leading layer axis, and ``layer(lw, x, control)`` computes one of
    them (a family with leading dense layers gives two stacks). A family
    whose layers are all alike gives only its feed-forward block
    ``ffn``: one stack ``"layers"`` of :func:`decoder_layer`."""

    def __init__(self, cfg: dict, weights: dict, ffn=None, stacks=None):
        self.cfg, self.w = cfg, weights
        eps = float(cfg["rms_norm_eps"])
        if stacks is None:
            stacks = [("layers", decoder_layer(cfg, ffn))]

        def indexed(layer):
            def run(layers, i, x, control):
                return layer(jax.tree.map(lambda a: a[i], layers), x,
                             control)
            return jax.jit(run, static_argnums=(3,))

        #: (key, jitted ``run(layers, i, x, control)``, number of layers)
        self.stacks = [(key, indexed(layer),
                        jax.tree.leaves(weights[key])[0].shape[0])
                       for key, layer in stacks]

        def embed(table, ids, control):
            return weight(table, control, axis=-1)[ids]

        def head(table, head_w, norm, x, control):
            x = rmsnorm(x, norm, eps)
            if head_w is None:                       # tied to the embedding
                return mm(x, weight(table, control, axis=-1).T)
            return mm(x, weight(head_w, control))

        self._embed = jax.jit(embed, static_argnums=(2,))
        self._head = jax.jit(head, static_argnums=(4,))

    def logits(self, ids, control: bool = False):
        """(P, vocab) float32 logits at every position of ``ids``."""
        w = self.w
        with jax.default_matmul_precision("highest"):
            x = self._embed(w["embed"], jnp.asarray(ids, jnp.int32), control)
            for key, layer, n in self.stacks:
                for i in range(n):
                    x = layer(w[key], jnp.int32(i), x, control)
            return self._head(w["embed"], w["head"], w["final_norm"], x,
                              control)


def gqa_work(c: dict, ffn_params: int) -> counts.Layer:
    """The work of one layer of this module's :func:`attention` at the
    configuration's widths, with a feed-forward block of
    ``ffn_params``."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return counts.gqa_layer(d, h, c["num_key_value_heads"],
                            c.get("head_dim") or d // h, bool(c["qkv_bias"]),
                            ffn_params)
