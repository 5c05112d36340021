"""Program mapping of the DeepSeek-V2 family: multi-head latent
attention with YaRN rope, ``first_k_dense_replace`` leading dense
layers, then mixture-of-experts layers of ``n_routed_experts`` held
experts (of a router over ``router_experts``, from
``first_held_expert`` on) and ``n_shared_experts`` shared ones,
routed group-limited greedy, gates not renormalised and times
``routed_scaling_factor``.

The weight view is the published layout (``modeling_deepseek``):
``q_a``/``q_b`` (q_a_proj, q_b_proj), ``kv_a`` (kv_a_proj_with_mqa:
the latent, then the shared rope key), ``kv_b`` (kv_b_proj: per head
the no-rope key, then the value), ``o``, and their two norm gains. The
program rotates the rope channels in halves (pair ``i`` is channels
``i`` and ``dr/2 + i``), the checkpoint pairs adjacent channels
(``2i``, ``2i + 1``); so the view permutes the rope columns of ``q_b``
and ``kv_a`` into the checkpoint's order, and the reference, which
rotates adjacent pairs, computes the same model.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.families import common
from repro.models.common import ModelConfig

_METHODS = ("greedy", "group_limited_greedy")


def model_config(c: dict, name: str) -> ModelConfig:
    if c["topk_method"] not in _METHODS:
        raise ValueError(f"{name}: topk_method {c['topk_method']!r}; the "
                         f"program routes {_METHODS}")
    if c["scoring_func"] != "softmax" or c["moe_layer_freq"] != 1:
        raise ValueError(f"{name}: the program scores experts by softmax, "
                         f"a MoE layer after every leading dense one")
    if not c["q_lora_rank"]:
        raise ValueError(f"{name}: only a low-rank q projection is mapped")
    grouped = c["topk_method"] == "group_limited_greedy"
    return ModelConfig(
        name=name, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        attn_type="mla", kv_lora_rank=c["kv_lora_rank"],
        q_lora_rank=c["q_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), rope_scaling=c["rope_scaling"],
        n_experts=c["router_experts"], experts_held=c["n_routed_experts"],
        expert_offset=c["first_held_expert"],
        top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        moe_d_ff=c["moe_intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"],
        n_group=c["n_group"] if grouped else 0,
        topk_group=c["topk_group"] if grouped else 0,
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"])


def _rope_order(dr: int) -> np.ndarray:
    """Program column of each checkpoint rope column: ``2i -> i``,
    ``2i + 1 -> dr/2 + i``."""
    return np.stack([np.arange(dr // 2), dr // 2 + np.arange(dr // 2)],
                    -1).reshape(-1)


def mla_view(layer, cfg: ModelConfig) -> dict:
    """Norm gains and latent attention of a layer (or a stack of them)
    in the published layout."""
    mix = layer["mixer"]
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    order = _rope_order(dr)
    w_uq = mix["w_uq"]["w"]
    lead = w_uq.shape[:-1]
    q_b = w_uq.reshape(*lead, H, dn + dr)
    q_b = jnp.concatenate([q_b[..., :dn], q_b[..., dn:][..., order]], -1)
    uk = mix["w_uk"]["w"].reshape(*lead[:-1], cfg.kv_lora_rank, H, dn)
    uv = mix["w_uv"]["w"].reshape(*uk.shape[:-1], cfg.v_head_dim)
    return {"attn_norm": layer["norm1"]["scale"],
            "mlp_norm": layer["norm2"]["scale"],
            "q_a": mix["w_dq"]["w"], "q_a_norm": mix["q_norm"]["scale"],
            "q_b": q_b.reshape(*lead, -1),
            "kv_a": jnp.concatenate(
                [mix["w_dkv"]["w"], mix["w_krope"]["w"][..., order]], -1),
            "kv_a_norm": mix["kv_norm"]["scale"],
            "kv_b": jnp.concatenate([uk, uv], -1).reshape(
                *uk.shape[:-2], -1),
            "o": mix["wo"]["w"]}


def reference_weights(params, cfg: ModelConfig) -> dict:
    (st,) = params["stack"]
    prefix = common.stack_layers([
        {**mla_view(p, cfg), **common.mlp_view(p["mlp"])}
        for p in params["prefix"]])
    layers = {**mla_view(st, cfg), **common.moe_view(st["moe"]),
              **common.mlp_view(st["moe"]["shared"], "shared_")}
    return common.decoder_weights(params, cfg, prefix=prefix, layers=layers)
