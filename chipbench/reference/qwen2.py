"""Plain float32 reference of the Qwen2 family (Qwen1.5 checkpoints).

Decoder layer: RMSNorm, causal multi-head attention with rotary
positions and a bias on q, k and v (none on o), residual; RMSNorm,
dense SwiGLU MLP ``down(silu(gate(x)) * up(x))``, residual. Final
RMSNorm, then the head, tied to the embedding where the configuration
says so. No departure from the published description is needed here;
the sliding window is off (``use_sliding_window: false``).

Work of a served call (``chipbench.counts``): every layer's attention,
q/k/v bias included, and its dense MLP.
"""

from __future__ import annotations

from chipbench import counts
from chipbench.reference.common import Reference, gqa_work, swiglu


def ffn(cfg: dict, lw: dict, h, control: bool):
    return swiglu(h, lw["gate"], lw["up"], lw["down"], control)


def reference(cfg: dict, weights: dict) -> Reference:
    return Reference(cfg, weights, ffn)


def work(c: dict) -> counts.Work:
    d = c["hidden_size"]
    layer = gqa_work(c, counts.swiglu_params(d, c["intermediate_size"]))
    return counts.decoder(d, c["vocab_size"],
                          [layer] * c["num_hidden_layers"])
