"""Smoke-width stand-ins for the benchmark's configurations and mixes:
the same keys and families, sizes a CPU test can hold."""

import copy
import json

from chipbench import spec


def _load(kind, name):
    with open(spec.BENCH_DIR / kind / f"{name}.json") as f:
        return json.load(f)


def qwen_config():
    c = _load("configs", "qwen1.5-0.5b")
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)
    return c


def llama_config():
    """A family the benchmark does not run: dense, no bias, untied head.
    Its reference is the file :data:`LLAMA_REFERENCE`."""
    c = qwen_config()
    for k in ("sliding_window", "use_sliding_window", "max_window_layers"):
        c.pop(k)
    c.update(architectures=["LlamaForCausalLM"], model_type="llama",
             qkv_bias=False, attention_bias=False, tie_word_embeddings=False,
             rms_norm_eps=1e-6, rope_theta=500000.0)
    return c


LLAMA_FAMILY = """from chipbench.families import common


def model_config(c, name):
    return common.gqa_decoder(c, name, d_ff=c["intermediate_size"])


def reference_weights(params, cfg):
    st = common.one_layer_unit(params)
    return common.decoder_weights(params, cfg, layers={
        **common.attention_view(st), **common.mlp_view(st["mlp"])})
"""

LLAMA_REFERENCE = """from chipbench import counts
from chipbench.reference.common import Reference, gqa_work, swiglu


def ffn(cfg, lw, h, control):
    return swiglu(h, lw["gate"], lw["up"], lw["down"], control)


def reference(cfg, weights):
    return Reference(cfg, weights, ffn)


def work(c):
    d = c["hidden_size"]
    layer = gqa_work(c, counts.swiglu_params(d, c["intermediate_size"]))
    return counts.decoder(d, c["vocab_size"],
                          [layer] * c["num_hidden_layers"])
"""


def toy_moe_config():
    """A family unlike the benchmark's two, for harness tests only: the
    mixture-of-experts key names of DeepSeek-V2's ``config.json``
    (routed and shared experts, a leading dense layer) at smoke widths,
    with the grouped-query attention and renormalised top-k gates that
    the program computes today. Its mapping and reference are
    :data:`TOY_MOE_FAMILY` and :data:`TOY_MOE_REFERENCE`."""
    return {
        "note": "a harness test, not a model: DeepSeek-V2's MoE key names "
                "at smoke widths, with GQA attention and renormalised "
                "top-k gates, which is what the program computes today",
        "architectures": ["ToyMoeForCausalLM"], "model_type": "toy_moe",
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "n_shared_experts": 1, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "attention_bias": False, "qkv_bias": False,
        "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


TOY_MOE_FAMILY = """from chipbench.families import common


def model_config(c, name):
    if not c["norm_topk_prob"]:
        raise ValueError(f"{name}: the program renormalises the top-k gates")
    return common.gqa_decoder(
        c, name, d_ff=c["intermediate_size"],
        n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        moe_d_ff=c["moe_intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"])


def reference_weights(params, cfg):
    (st,) = params["stack"]
    prefix = common.stack_layers([
        {**common.attention_view(p), **common.mlp_view(p["mlp"])}
        for p in params["prefix"]])
    layers = {**common.attention_view(st), **common.moe_view(st["moe"]),
              **common.mlp_view(st["moe"]["shared"], "shared_")}
    return common.decoder_weights(params, cfg, prefix=prefix, layers=layers)
"""

TOY_MOE_REFERENCE = """import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.reference.common import (Reference, decoder_layer, gqa_work,
                                        mm, swiglu, weight)


def dense(cfg, lw, h, control):
    return swiglu(h, lw["gate"], lw["up"], lw["down"], control)


def moe(cfg, lw, h, control):
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(h, weight(lw["router"], control)), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    y = swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
               control)
    for e in range(cfg["n_routed_experts"]):
        y = y + gate[:, e, None] * swiglu(
            h, lw["experts_gate"][e], lw["experts_up"][e],
            lw["experts_down"][e], control)
    return y


def reference(cfg, weights):
    return Reference(cfg, weights, stacks=[
        ("prefix", decoder_layer(cfg, dense)),
        ("layers", decoder_layer(cfg, moe))])


def work(c):
    d, k = c["hidden_size"], c["first_k_dense_replace"]
    first = gqa_work(c, counts.swiglu_params(d, c["intermediate_size"]))
    ff = c["moe_intermediate_size"]
    rest = gqa_work(c, counts.routed_params(
        d, c["n_routed_experts"], c["num_experts_per_tok"], ff) +
        counts.swiglu_params(d, c["n_shared_experts"] * ff))
    return counts.decoder(d, c["vocab_size"], [first] * k +
                          [rest] * (c["num_hidden_layers"] - k))
"""


def mixtral_config():
    c = _load("configs", "mixtral-8x7b-l2")
    c.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=2, num_local_experts=4, vocab_size=256)
    return c


def open_mix():
    m = _load("traffic", "short-chat-open")
    m.update(rate_per_s=6.0, max_len=96,
             prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                         "min": 4, "max": 48},
             output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 2, "max": 24},
             check={"min_tokens": 30, "max_requests": 6},
             trace={"start_s": 0.5, "length_s": 0.5})
    return m


def closed_mix():
    m = _load("traffic", "long-prompt-closed")
    m.update(concurrency=4, block=4, requests_per_epoch=16, max_len=96,
             prompt_len={"dist": "lognormal", "median": 16, "sigma": 0.5,
                         "min": 4, "max": 48},
             output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 3, "max": 24},
             check={"min_tokens": 60, "max_requests": 12},
             trace={"start_s": 0.5, "length_s": 0.5})
    return m


def decode_mix():
    m = _load("traffic", "short-prompt-long-output-closed")
    m.update(concurrency=4, block=4, requests_per_epoch=16, max_len=64,
             prompt_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 3, "max": 12},
             output_len={"dist": "lognormal", "median": 16, "sigma": 0.6,
                         "min": 8, "max": 48},
             check={"min_tokens": 200, "max_requests": 16},
             trace={"start_s": 0.5, "length_s": 0.5})
    return m


#: at smoke widths the bf16 dense program reads gaps of a few hundredths
LIMITS = {"checks": {"max_logit_gap": {"max": 0.25},
                     "tokens_compared": {"min": 10}}}

#: the decode cell's smoke stand-in, aged as its mix sets, over 200 or
#: more served tokens: the bf16 program reads widest gaps of
#: 0.0003-0.0053, the float8 control 0.032-0.099 (9 seeds on the CPU)
DECODE_LIMITS = {"checks": {"max_logit_gap": {"max": 0.012},
                            "tokens_compared": {"min": 100}}}

#: a mixture of experts compares the mismatch share, as its cell does:
#: at smoke widths the bf16 program reads 0.02-0.06 (a routing near-tie
#: can still swing its widest gap past 1), the float8 control 0.14-0.32
MOE_LIMITS = {"checks": {"mismatch_share": {"max": 0.1},
                         "tokens_compared": {"min": 10}}}


def cell(name, config, mix, limits=LIMITS):
    bench = spec.load_benchmark()
    return spec.Cell(name=name, chips=1, config_name=name + "-smoke",
                     config=config, traffic=mix,
                     end_to_end=spec._for_cell(bench["end_to_end"], name),
                     per_layer=spec._for_cell(bench["per_layer"], name),
                     limits=copy.deepcopy(limits))
