"""The work a served call needs, counted from the configuration's shapes.

One call is one batch-1 decoder step at position ``pos``: ``pos``
tokens are already in the cache and the call attends over ``pos + 1``.
What it needs, whatever the program happens to do:

* FLOPs: 2 per weight it multiplies (for a mixture of experts, the
  router and only the ``k`` experts the token is routed to), plus the
  attention scores and the weighted sum over ``pos + 1`` positions.
* Bytes: every weight it needs once (the same top-``k`` experts, one
  row of the embedding, the head), the cache up to its position, and
  the one new cache entry it writes. Activations are left out: at
  batch 1 they are some kilobytes.

A program that reads all experts, or the whole preallocated cache,
reads more than this; its share of the roofline shows how much more.

Each family counts its own call from its configuration's keys:
``reference/<model_type>.py`` exports ``work(c)``, a :class:`Work`
built from the decoder formulas here, which take numbers and read no
configuration key. :func:`call_flops` and :func:`call_bytes` add the
position.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

__all__ = ["Layer", "Work", "gqa_layer", "swiglu_params", "routed_params",
           "decoder", "call_flops", "call_bytes", "least_time",
           "peaks_for"]

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Layer:
    """One decoder layer at batch 1."""
    params: int                 # weights a token multiplies in it
    attn_flops_per_key: int     # scores and weighted sum, per position
    cache_bytes_per_token: int  # what it caches per position


@dataclass(frozen=True)
class Work:
    """What one call needs besides its position."""
    multiplied: int             # weights multiplied, 2 FLOPs each
    read: int                   # weights read once
    attn_flops_per_key: int
    cache_bytes_per_token: int
    weight_bytes: int = 2       # bf16


def gqa_layer(d: int, heads: int, kv_heads: int, head_dim: int, bias: bool,
              ffn_params: int, cache_bytes: int = 2) -> Layer:
    """A layer of grouped-query attention (q, k, v and o projections, an
    optional bias on q, k and v, a key/value cache in ``cache_bytes``
    per value) and a feed-forward block of ``ffn_params``."""
    q = d * heads * head_dim
    kv = 2 * d * kv_heads * head_dim
    b = (heads + 2 * kv_heads) * head_dim if bias else 0
    return Layer(params=q + kv + q + b + ffn_params,
                 attn_flops_per_key=4 * heads * head_dim,
                 cache_bytes_per_token=2 * kv_heads * head_dim * cache_bytes)


def swiglu_params(d: int, ff: int) -> int:
    """Gate, up and down projections of width ``ff``."""
    return 3 * d * ff


def routed_params(d: int, experts: int, top_k: int, ff: int) -> int:
    """The router over ``experts`` and the ``top_k`` SwiGLU experts of
    width ``ff`` a token is sent to."""
    return d * experts + top_k * swiglu_params(d, ff)


def decoder(d: int, vocab: int, layers: list[Layer]) -> Work:
    """A pre-norm decoder of ``layers``: two norm gains a layer and a
    final one are read; one embedding row is read; the head is
    multiplied."""
    body = sum(x.params for x in layers)
    return Work(multiplied=body + vocab * d,
                read=body + (2 * len(layers) + 1) * d + vocab * d + d,
                attn_flops_per_key=sum(x.attn_flops_per_key for x in layers),
                cache_bytes_per_token=sum(x.cache_bytes_per_token
                                          for x in layers))


def call_flops(w: Work, pos: int) -> float:
    return 2.0 * w.multiplied + w.attn_flops_per_key * (pos + 1)


def call_bytes(w: Work, pos: int) -> float:
    cache = (pos + 1) * w.cache_bytes_per_token + w.cache_bytes_per_token
    return float(w.read * w.weight_bytes + cache)


def least_time(w: Work, pos: int, peaks: dict) -> float:
    """Seconds the chip needs at least for one call: the larger of its
    FLOPs over peak FLOP/s and its bytes over peak bytes/s."""
    return max(call_flops(w, pos) / peaks["bf16_flops_per_s"],
               call_bytes(w, pos) / peaks["hbm_bytes_per_s"])


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
