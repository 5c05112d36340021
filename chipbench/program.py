"""The benchmark's side of the system under test.

The program is :mod:`repro`: its ``ModelConfig``, the parameter layout
that ``repro.models.transformer.init`` defines, and
``repro.serve.ServingEngine``. This module maps a configuration file
(the published ``config.json`` keys, as run) onto them, makes the
weights from the seed, and gives the reference a plain per-layer view
of those weights. Nothing here computes a result the check compares.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.serve import ServingEngine, SchedulerPolicy

__all__ = ["PROGRAM_RMS_EPS", "model_config", "make_params", "build_engine",
           "reference_weights"]

#: the program's RMSNorm eps (``repro.models.common.rmsnorm``); it has
#: no option for another, so a configuration must state this one
PROGRAM_RMS_EPS = 1e-6


def model_config(c: dict, name: str) -> ModelConfig:
    """The program's ``ModelConfig`` for configuration file ``c``.

    Besides the published ``config.json`` keys, the file states
    ``qkv_bias``: whether the q, k and v projections carry a bias (Qwen2
    checkpoints do, and their ``config.json`` has no key for it)."""
    if c["hidden_act"] != "silu":
        raise ValueError(f"{name}: the program's MLP is SwiGLU (silu)")
    if c["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"{name}: rms_norm_eps {c['rms_norm_eps']} but the "
                         f"program's RMSNorm is fixed at {PROGRAM_RMS_EPS}")
    if c.get("attention_bias"):
        raise ValueError(f"{name}: the program has no bias on the attention "
                         f"output projection")
    n_exp = int(c.get("num_local_experts", 0))
    window = c.get("sliding_window")
    if not c.get("use_sliding_window", True):
        window = None
    return ModelConfig(
        name=name, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or
        c["hidden_size"] // c["num_attention_heads"],
        d_ff=0 if n_exp else c["intermediate_size"], vocab=c["vocab_size"],
        qkv_bias=bool(c["qkv_bias"]), sliding_window=window,
        rope_theta=float(c["rope_theta"]), n_experts=n_exp,
        top_k=int(c.get("num_experts_per_tok", 0)),
        moe_d_ff=c["intermediate_size"] if n_exp else 0,
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"])


def _leaf_scale(names: list[str], shape: tuple, n_layers: int) -> tuple:
    """(mean, std) of one parameter, by its place in the tree."""
    last = names[-1]
    if last == "scale":                       # RMSNorm gains
        return 1.0, 0.1
    if last == "b":                           # q/k/v biases
        return 0.0, 0.1
    if names[0] == "embed":
        return 0.0, 0.02
    std = 1.0 / math.sqrt(shape[-2])          # fan-in
    if "wo" in names or "w_down" in names:    # projections back into the
        std /= math.sqrt(2 * n_layers)        # residual stream
    return 0.0, std


def make_params(cfg: ModelConfig, seed: int, dtype=jnp.bfloat16):
    """The program's parameter tree, random from ``seed`` (any whole
    number up to 2**63), in ``dtype``, made on the device in one jitted
    call."""
    shapes = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            names = [getattr(p, "key", None) for p in path]
            names = [n for n in names if isinstance(n, str)]
            mean, std = _leaf_scale(names, sd.shape, cfg.n_layers)
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            out.append((mean + std * z).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return jax.jit(gen)(key)


def build_engine(cfg: ModelConfig, params, max_len: int) -> ServingEngine:
    """The engine as a deployment runs it: the default policy."""
    return ServingEngine(cfg, params, max_len=max_len,
                         policy=SchedulerPolicy())


def reference_weights(params, cfg: ModelConfig) -> dict:
    """A plain view of the program's weights for the reference: arrays
    named by what they are, ``x @ W`` orientation, every layer's
    arrays stacked on a leading layer axis; ``head`` is None where the
    head is the embedding, tied."""
    if params["prefix"]:
        raise ValueError("leading dense layers are not mapped")
    if len(params["stack"]) != 1:
        raise ValueError("only a one-layer repeating unit is mapped")
    st = params["stack"][0]
    mix = st["mixer"]
    layers = {"attn_norm": st["norm1"]["scale"],
              "mlp_norm": st["norm2"]["scale"]}
    for k in ("wq", "wk", "wv", "wo"):
        layers[k] = mix[k]["w"]
        if "b" in mix[k]:
            layers["b" + k[1]] = mix[k]["b"]
    if "moe" in st:
        layers["router"] = st["moe"]["router"]["w"]
        for k in ("w_gate", "w_up", "w_down"):
            layers["experts_" + k[2:]] = st["moe"]["experts"][k]
    else:
        for k in ("w_gate", "w_up", "w_down"):
            layers[k[2:]] = st["mlp"][k]["w"]
    head = None if cfg.tie_embeddings else params["lm_head"]["w"]
    return {"embed": params["embed"]["w"],
            "final_norm": params["final_norm"]["scale"], "head": head,
            "layers": layers}
