"""Plain float32 reference of the Mixtral family.

Decoder layer: RMSNorm, causal grouped-query attention (32 query heads
over 8 key/value heads at the published widths) with rotary positions
and no bias, the sliding window where the configuration sets one,
residual; RMSNorm, sparse mixture of experts, residual. Final RMSNorm
and an untied head.

Mixture of experts as published: router logits ``h @ W_r``; routing
weights are the softmax over all experts, the top ``k`` kept and
renormalised to sum to 1; each expert is a SwiGLU ``w2(silu(w1 h) *
w3 h)``; the output is the weighted sum of the chosen experts. No
token is ever dropped. Computed densely, one expert at a time over all
positions, with the weight of an expert a position did not choose set
to 0, so that one expert's float32 weights are in memory at a time.

Work of a served call (``chipbench.counts``): every layer's attention,
its router, and the ``k`` experts a token is routed to, not all ``E``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.reference.common import Reference, gqa_work, mm, swiglu, \
    weight


def ffn(cfg: dict, lw: dict, h, control: bool):
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(h, weight(lw["router"], control)), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)          # (P, E)

    def one(y, e):
        g, u, d = (jax.lax.dynamic_index_in_dim(lw[n], e, 0, False)
                   for n in ("experts_gate", "experts_up", "experts_down"))
        return y + gate[:, e, None] * swiglu(h, g, u, d, control), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(E))
    return y


def reference(cfg: dict, weights: dict) -> Reference:
    return Reference(cfg, weights, ffn)


def work(c: dict) -> counts.Work:
    d = c["hidden_size"]
    layer = gqa_work(c, counts.routed_params(
        d, c["num_local_experts"], c["num_experts_per_tok"],
        c["intermediate_size"]))
    return counts.decoder(d, c["vocab_size"],
                          [layer] * c["num_hidden_layers"])


def router_margins(ref: Reference, ids):
    """(layers, P) numpy: at each layer and position, how far the
    weakest chosen expert's routing weight lies above the strongest one
    left out. Near 0, a rounding can swap the two: a diagnostic for a
    served token far from the reference's best."""
    import numpy as np

    from chipbench.reference.common import attention, rmsnorm
    cfg, w = ref.cfg, ref.w
    k, eps = cfg["num_experts_per_tok"], float(cfg["rms_norm_eps"])

    @jax.jit
    def margin(layers, i, x):
        lw = jax.tree.map(lambda a: a[i], layers)
        h = rmsnorm(x, lw["attn_norm"], eps)
        x = x + attention(cfg, lw, h, False)
        h = rmsnorm(x, lw["mlp_norm"], eps)
        p = jnp.sort(jax.nn.softmax(mm(h, weight(lw["router"], False)), -1),
                     -1)[:, ::-1]
        return p[:, k - 1] - p[:, k]

    (key, layer, n), = ref.stacks
    out = []
    with jax.default_matmul_precision("highest"):
        x = ref._embed(w["embed"], jnp.asarray(ids, jnp.int32), False)
        for i in range(n):
            out.append(np.asarray(margin(w[key], jnp.int32(i), x)))
            x = layer(w[key], jnp.int32(i), x, False)
    return np.stack(out)
