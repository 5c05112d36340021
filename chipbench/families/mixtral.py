"""Program mapping of the Mixtral family: every layer a sparse mixture
of ``num_local_experts`` SwiGLU experts of width ``intermediate_size``,
top ``num_experts_per_tok``, and no dense MLP."""

from __future__ import annotations

from chipbench.families import common


def model_config(c: dict, name: str):
    return common.gqa_decoder(c, name, d_ff=0,
                              n_experts=int(c["num_local_experts"]),
                              top_k=int(c["num_experts_per_tok"]),
                              moe_d_ff=c["intermediate_size"])


def reference_weights(params, cfg) -> dict:
    st = common.one_layer_unit(params)
    return common.decoder_weights(params, cfg, layers={
        **common.attention_view(st), **common.moe_view(st["moe"])})
