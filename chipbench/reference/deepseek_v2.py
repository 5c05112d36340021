"""Plain float32 reference of the DeepSeek-V2 family.

Written from the published model (``modeling_deepseek`` of
deepseek-ai/DeepSeek-V2 and arXiv:2405.04434), one sequence, every
position at once, causal, no cache and no absorption.

Decoder layer: RMSNorm, multi-head latent attention, residual; RMSNorm,
a dense SwiGLU MLP (the ``first_k_dense_replace`` leading layers) or
the mixture of experts, residual. Final RMSNorm and an untied head.

Latent attention in its naive form: ``q = q_b(norm(q_a(x)))`` per head
(no-rope part, then rope part); ``kv_a(x)`` gives the latent ``c``
(normed) and one rope key shared by every head; ``kv_b(c)`` expands
each head's no-rope key and its value. Rope is YaRN
(``rope_scaling``): frequencies of pairs that turn fewer than
``beta_slow`` times over the original context divided by ``factor``,
a linear ramp up to ``beta_fast``, cos and sin times ``mscale(f,
mscale) / mscale(f, mscale_all_dim)``, applied to adjacent channel
pairs as the checkpoint stores them; the softmax scale is
``mscale(f, mscale_all_dim)**2 / sqrt(qk_nope + qk_rope)``.

Mixture of experts as published: a softmax over the router's
``router_experts``; with ``group_limited_greedy`` each of ``n_group``
groups scores its best expert and only the ``topk_group`` best groups
are kept; the top ``num_experts_per_tok`` of those are chosen; their
gates are renormalised where ``norm_topk_prob`` (and ``k > 1``), else
times ``routed_scaling_factor``. Only the ``n_routed_experts`` experts
held here, from ``first_held_expert`` on, are computed (densely, one
at a time over all positions, a gate of 0 where a position did not
choose it): the absent experts' part is left out, as in the program.
The ``n_shared_experts`` shared experts are one SwiGLU of
``n_shared_experts`` times the expert width, added for every token.

Work of a served call (``chipbench.counts``): every layer's latent
attention in its absorbed decode form (projections; per cached
position, scores over the latent and the rope key and the weighted sum
of the latent), the dense MLP or the router, the shared experts and
the held experts a token is expected to reach, ``k * held / router``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts
from chipbench.reference.common import (HI, Reference, decoder_layer, mm,
                                        rmsnorm, swiglu, weight)

#: eps of the two latent norms: ``DeepseekV2RMSNorm``'s default, which
#: ``q_a_layernorm`` and ``kv_a_layernorm`` are built with
_LATENT_NORM_EPS = 1e-6


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rope_table(cfg: dict, P: int):
    """(P, dr/2) cos and sin of ``DeepseekV2YarnRotaryEmbedding`` (or of
    plain rope where ``rope_scaling`` is null)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    m = 1.0
    s = cfg.get("rope_scaling")
    if s:
        f, orig = float(s["factor"]), s["original_max_position_embeddings"]

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(corr(s["beta_fast"])), 0)
        high = min(math.ceil(corr(s["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        mask = 1.0 - ramp                         # inv_freq_mask
        inv = inv / f * (1 - mask) + inv * mask
        m = yarn_get_mscale(f, s["mscale"]) / yarn_get_mscale(
            f, s["mscale_all_dim"])
    ang = np.arange(P)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def softmax_scale(cfg: dict) -> float:
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    s = cfg.get("rope_scaling")
    if s and s.get("mscale_all_dim"):
        scale *= yarn_get_mscale(float(s["factor"]), s["mscale_all_dim"]) ** 2
    return scale


def rope(x, cos, sin):
    """Rotate adjacent channel pairs ``(2i, 2i + 1)`` by pair ``i``'s
    angle. x: (P, H, dr)."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def attention(cfg: dict, lw: dict, x, control: bool):
    """Causal multi-head latent attention, naive: keys and values
    expanded from the latent."""
    P = x.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = mm(rmsnorm(mm(x, weight(lw["q_a"], control)), lw["q_a_norm"],
                   _LATENT_NORM_EPS),
           weight(lw["q_b"], control)).reshape(P, H, dn + dr)
    kv_a = mm(x, weight(lw["kv_a"], control))
    c_kv = rmsnorm(kv_a[:, :r], lw["kv_a_norm"], _LATENT_NORM_EPS)
    kv = mm(c_kv, weight(lw["kv_b"], control)).reshape(P, H, dn + dv)
    cos, sin = _rope_table(cfg, P)
    q_pe = rope(q[..., dn:], cos, sin)
    k_pe = rope(kv_a[:, None, r:], cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (P, H, dr))],
                        -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * softmax_scale(cfg)
    pos = jnp.arange(P)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kv[..., dn:], precision=HI)
    return mm(o.reshape(P, H * dv), weight(lw["o"], control))


def dense(cfg: dict, lw: dict, h, control: bool):
    return swiglu(h, lw["gate"], lw["up"], lw["down"], control)


def gates(cfg: dict, probs):
    """(P, router) gate of each expert at each position: 0 where the
    position did not choose it."""
    P, E = probs.shape
    k = cfg["num_experts_per_tok"]
    scores = probs
    if cfg["topk_method"] == "group_limited_greedy":
        G = cfg["n_group"]
        group = probs.reshape(P, G, E // G).max(-1)
        _, best = jax.lax.top_k(group, cfg["topk_group"])
        keep = jnp.zeros((P, G)).at[jnp.arange(P)[:, None], best].set(1.0)
        scores = jnp.where(jnp.repeat(keep, E // G, 1) > 0, probs, 0.0)
    elif cfg["topk_method"] != "greedy":
        raise ValueError(f"topk_method {cfg['topk_method']!r}")
    top, idx = jax.lax.top_k(scores, k)
    if k > 1 and cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    else:
        top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(probs).at[jnp.arange(P)[:, None], idx].set(top)


def moe(cfg: dict, lw: dict, h, control: bool):
    gate = gates(cfg, jax.nn.softmax(mm(h, weight(lw["router"], control)),
                                     axis=-1))
    first = cfg.get("first_held_expert", 0)
    y = swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
               control)

    def one(y, e):
        g, u, d = (jax.lax.dynamic_index_in_dim(lw[n], e, 0, False)
                   for n in ("experts_gate", "experts_up", "experts_down"))
        return y + jax.lax.dynamic_index_in_dim(gate, first + e, 1) * \
            swiglu(h, g, u, d, control), None

    y, _ = jax.lax.scan(one, y, jnp.arange(cfg["n_routed_experts"]))
    return y


def reference(cfg: dict, weights: dict) -> Reference:
    return Reference(cfg, weights, stacks=[
        ("prefix", decoder_layer(cfg, dense, attention)),
        ("layers", decoder_layer(cfg, moe, attention))])


def mla_layer(c: dict, ffn_params: int) -> counts.Layer:
    """A layer of absorbed latent attention with a feed-forward block of
    ``ffn_params``: the q, kv and o projections with the two latent norm
    gains; per cached position, scores over the latent and the rope key
    and the weighted sum of the latent, in every head; the latent and
    the rope key cached in bf16."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    rq, r, dr = c["q_lora_rank"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    dn, dv = c["qk_nope_head_dim"], c["v_head_dim"]
    proj = (d * rq + rq + rq * H * (dn + dr) + d * (r + dr) + r +
            r * H * (dn + dv) + H * dv * d)
    return counts.Layer(params=proj + ffn_params,
                        attn_flops_per_key=2 * H * (r + dr) + 2 * H * r,
                        cache_bytes_per_token=2 * (r + dr))


def work(c: dict) -> counts.Work:
    d, k = c["hidden_size"], c["first_k_dense_replace"]
    ff, E = c["moe_intermediate_size"], c.get("router_experts",
                                              c["n_routed_experts"])
    expert = counts.swiglu_params(d, ff)
    held = c["num_experts_per_tok"] * c["n_routed_experts"] * expert // E
    first = mla_layer(c, counts.swiglu_params(d, c["intermediate_size"]))
    rest = mla_layer(c, d * E + held + c["n_shared_experts"] * expert)
    return counts.decoder(d, c["vocab_size"], [first] * k +
                          [rest] * (c["num_hidden_layers"] - k))
