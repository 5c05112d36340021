"""Pieces of the program mapping that the decoder families share.

A family's file, ``chipbench/families/<model_type>.py``, exports

* ``model_config(c, name) -> ModelConfig``: its configuration file
  (the published ``config.json`` keys, as run) as the program's config;
* ``reference_weights(params, cfg) -> dict``: the program's parameter
  tree as the plain view its reference (``chipbench/reference/
  <model_type>.py``) reads: arrays named by what they are, ``x @ W``
  orientation, each stack's layers on a leading layer axis, and
  ``head`` None where the head is the embedding, tied.

Those two functions are built from the helpers here. The facts of the
program that hold for every family (its RMSNorm eps, SwiGLU, no bias on
the attention output) are checked in ``chipbench/program.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig

__all__ = ["gqa_decoder", "one_layer_unit", "attention_view", "mlp_view",
           "moe_view", "stack_layers", "decoder_weights"]


def gqa_decoder(c: dict, name: str, **ffn) -> ModelConfig:
    """A decoder of grouped-query attention with rotary positions; the
    feed-forward fields (``d_ff``, ``n_experts``, ...) come from the
    family as ``ffn``.

    Besides the published keys, the file states ``qkv_bias``: whether
    the q, k and v projections carry a bias (no ``config.json`` of
    these families has a key for it)."""
    window = c.get("sliding_window")
    if not c.get("use_sliding_window", True):
        window = None
    return ModelConfig(
        name=name, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or
        c["hidden_size"] // c["num_attention_heads"],
        vocab=c["vocab_size"], qkv_bias=bool(c["qkv_bias"]),
        sliding_window=window, rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], **ffn)


def one_layer_unit(params) -> dict:
    """The program's repeating unit where the model is one kind of
    layer with no leading layers apart."""
    if params["prefix"]:
        raise ValueError("leading dense layers are not mapped")
    if len(params["stack"]) != 1:
        raise ValueError("only a one-layer repeating unit is mapped")
    return params["stack"][0]


def attention_view(layer) -> dict:
    """Norm gains and attention weights of a layer (or a stack of them)."""
    mix = layer["mixer"]
    out = {"attn_norm": layer["norm1"]["scale"],
           "mlp_norm": layer["norm2"]["scale"]}
    for k in ("wq", "wk", "wv", "wo"):
        out[k] = mix[k]["w"]
        if "b" in mix[k]:
            out["b" + k[1]] = mix[k]["b"]
    return out


def mlp_view(mlp, label: str = "") -> dict:
    """A dense SwiGLU block: ``gate``, ``up``, ``down``."""
    return {label + k[2:]: mlp[k]["w"] for k in ("w_gate", "w_up", "w_down")}


def moe_view(moe) -> dict:
    """The router and the routed experts' stacked matrices."""
    out = {"router": moe["router"]["w"]}
    for k in ("w_gate", "w_up", "w_down"):
        out["experts_" + k[2:]] = moe["experts"][k]
    return out


def stack_layers(layers: list[dict]) -> dict:
    """Per-layer views on a new leading layer axis."""
    return jax.tree.map(lambda *a: jnp.stack(a), *layers)


def decoder_weights(params, cfg: ModelConfig, **stacks) -> dict:
    """Embedding, final norm and head, with the family's ``stacks``."""
    head = None if cfg.tie_embeddings else params["lm_head"]["w"]
    return {"embed": params["embed"]["w"],
            "final_norm": params["final_norm"]["scale"], "head": head,
            **stacks}
