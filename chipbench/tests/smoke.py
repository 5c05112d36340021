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


LLAMA_REFERENCE = """from chipbench.reference.common import Reference, swiglu


def ffn(cfg, lw, h, control):
    return swiglu(h, lw["gate"], lw["up"], lw["down"], control)


def reference(cfg, weights):
    return Reference(cfg, weights, ffn)
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


#: at smoke widths the bf16 dense program reads gaps of a few hundredths
LIMITS = {"checks": {"max_logit_gap": {"max": 0.25},
                     "tokens_compared": {"min": 10}}}

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
