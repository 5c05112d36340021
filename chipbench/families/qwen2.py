"""Program mapping of the Qwen2 family (Qwen1.5 checkpoints): dense
grouped-query decoder, the MLP of width ``intermediate_size``."""

from __future__ import annotations

from chipbench.families import common


def model_config(c: dict, name: str):
    return common.gqa_decoder(c, name, d_ff=c["intermediate_size"])


def reference_weights(params, cfg) -> dict:
    st = common.one_layer_unit(params)
    return common.decoder_weights(params, cfg, layers={
        **common.attention_view(st), **common.mlp_view(st["mlp"])})
