"""The work a served call needs, counted from the configuration's shapes.

One call is one batch-1 decoder step at position ``pos``: ``pos``
tokens are already in the key/value cache and the call attends over
``pos + 1``. What it needs, whatever the program happens to do:

* FLOPs: 2 per weight it multiplies (for a mixture of experts, the
  router and only the ``k`` experts the token is routed to), plus the
  attention scores and the weighted sum over ``pos + 1`` positions.
* Bytes: every weight it needs once (the same top-``k`` experts, one
  row of the embedding, the head), the key/value cache up to its
  position, and the one new key/value entry it writes. Activations are
  left out: at batch 1 they are some kilobytes.

A program that reads all experts, or the whole preallocated cache,
reads more than this; its share of the roofline shows how much more.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

__all__ = ["Shape", "shape_of", "call_flops", "call_bytes", "least_time",
           "peaks_for"]

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int            # 0: dense feed-forward
    top_k: int
    qkv_bias: bool
    tied: bool
    weight_bytes: int = 2   # bf16
    cache_bytes: int = 2    # bf16 key/value cache

    @property
    def attn_params(self) -> int:
        q = self.d * self.heads * self.head_dim
        kv = 2 * self.d * self.kv_heads * self.head_dim
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return q + kv + q + bias

    @property
    def ffn_active_params(self) -> int:
        if self.experts:
            return self.d * self.experts + self.top_k * 3 * self.d * self.ff
        return 3 * self.d * self.ff

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * \
            self.cache_bytes


def shape_of(c: dict) -> Shape:
    """The shape of a configuration file (published key names, and
    ``qkv_bias``)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Shape(layers=c["num_hidden_layers"], d=d, heads=h,
                 kv_heads=c["num_key_value_heads"],
                 head_dim=c.get("head_dim") or d // h,
                 ff=c["intermediate_size"], vocab=c["vocab_size"],
                 experts=int(c.get("num_local_experts", 0)),
                 top_k=int(c.get("num_experts_per_tok", 0)),
                 qkv_bias=bool(c["qkv_bias"]),
                 tied=bool(c["tie_word_embeddings"]))


def call_flops(s: Shape, pos: int) -> float:
    matmul = s.layers * (s.attn_params + s.ffn_active_params) + \
        s.vocab * s.d
    attn = s.layers * 4 * s.heads * s.head_dim * (pos + 1)
    return 2.0 * matmul + attn


def call_bytes(s: Shape, pos: int) -> float:
    norms = (2 * s.layers + 1) * s.d
    weights = s.layers * (s.attn_params + s.ffn_active_params) + norms + \
        s.vocab * s.d + s.d                        # head, embedding row
    kv = (pos + 1) * s.kv_bytes_per_token + s.kv_bytes_per_token
    return float(weights * s.weight_bytes + kv)


def least_time(s: Shape, pos: int, peaks: dict) -> float:
    """Seconds the chip needs at least for one call: the larger of its
    FLOPs over peak FLOP/s and its bytes over peak bytes/s."""
    return max(call_flops(s, pos) / peaks["bf16_flops_per_s"],
               call_bytes(s, pos) / peaks["hbm_bytes_per_s"])


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
