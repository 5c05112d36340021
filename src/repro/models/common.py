"""Shared model config, initialisers and elementary layers (pure JAX).

No flax/haiku: parameters are plain nested dicts of ``jnp.ndarray``;
every layer is an ``init(key, ...) -> params`` / ``apply(params, x)``
pair.  Sharding is assigned separately by path rules
(:mod:`repro.dist.sharding`), keeping model code mesh-agnostic.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ModelConfig", "Dense", "rmsnorm", "layernorm", "norm",
           "init_norm", "act_fn", "rope_tables", "apply_rope",
           "yarn_mscale", "yarn_inv_freq", "make_dense", "dense", "PyTree"]

PyTree = Any


@dataclass(frozen=True)
class ModelConfig:
    """One config object covers all ten assigned architectures."""

    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    # --- attention ---
    attn_type: str = "gqa"          # "gqa" | "mla"
    qkv_bias: bool = False
    causal: bool = True
    sliding_window: int | None = None
    rope_theta: float = 10_000.0
    use_rope: bool = True
    #: None, or the published ``rope_scaling`` dict (``type`` "yarn");
    #: held as its sorted items, so that the config stays hashable
    rope_scaling: Any = None

    # --- MLA (deepseek-v2) ---
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    moe_layer_period: int = 1        # MoE every k-th layer
    first_dense_layers: int = 0      # leading dense layers (deepseek)
    capacity_factor: float = 1.25
    n_group: int = 0                 # routing groups; 0: no grouping
    topk_group: int = 0              # groups a token may route into
    norm_topk_prob: bool = True      # renormalise the top-k gates
    routed_scaling_factor: float = 1.0
    #: the routed experts this layer holds: ``experts_held`` of the
    #: router's ``n_experts`` from ``expert_offset`` on (0: all of them)
    experts_held: int = 0
    expert_offset: int = 0

    # --- block pattern, cycled over layers ---
    block_pattern: tuple[str, ...] = ("attn",)   # attn|mamba|mlstm|slstm

    # --- mamba ---
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0           # 0 => ceil(d_model/16)

    # --- xlstm ---
    xlstm_proj_factor: float = 2.0

    # --- misc ---
    act: str = "swiglu"              # "swiglu" | "gelu"
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    input_mode: str = "tokens"       # "tokens" | "embeddings" (stub frontend)
    logit_softcap: float = 0.0

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))

    # ------------------------------------------------------------------
    @property
    def n_held(self) -> int:
        """Routed experts whose matrices this layer holds."""
        return self.experts_held or self.n_experts

    @property
    def expert_share(self) -> bool:
        """Whether the layer holds only a share of the router's experts."""
        return self.n_held < self.n_experts

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.d_model // 16)

    def layer_kind(self, i: int) -> str:
        return self.block_pattern[i % len(self.block_pattern)]

    def is_moe_layer(self, i: int) -> bool:
        if self.n_experts == 0 or i < self.first_dense_layers:
            return False
        return (i - self.first_dense_layers + 1) % self.moe_layer_period == 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Elementary layers
# ---------------------------------------------------------------------------

def make_dense(key, d_in: int, d_out: int, *, bias: bool = False,
               scale: float | None = None, dtype=jnp.float32) -> PyTree:
    scale = 1.0 / math.sqrt(d_in) if scale is None else scale
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


Dense = (make_dense, dense)  # convenience export


def init_norm(d: int, kind: str) -> PyTree:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def rmsnorm(p: PyTree, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(dt)


def layernorm(p: PyTree, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p.get("bias", 0.0)).astype(dt)


def norm(p: PyTree, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    return rmsnorm(p, x) if kind == "rmsnorm" else layernorm(p, x)


def act_fn(kind: str):
    if kind == "gelu":
        return jax.nn.gelu
    if kind == "silu":
        return jax.nn.silu
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor for a context stretched ``factor`` times
    (``yarn_get_mscale`` of DeepSeek-V2's modelling code)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, scaling) -> np.ndarray:
    """(head_dim//2,) rotary frequencies under YaRN: pairs that turn
    fewer than ``beta_slow`` times over the original context are
    divided by ``factor``, pairs that turn more than ``beta_fast``
    times are kept, with a linear ramp between."""
    s = dict(scaling)
    f, orig = float(s["factor"]), s["original_max_position_embeddings"]

    def dim_of(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(s.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim_of(s.get("beta_slow", 1))), head_dim - 1)
    if low == high:
        high += 0.001
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return extra / f * ramp + extra * (1.0 - ramp)


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float,
                scaling=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for the given positions; shape (..., head_dim//2).
    ``scaling``: a YaRN ``rope_scaling`` (its frequencies, and cos/sin
    times ``mscale(f, mscale) / mscale(f, mscale_all_dim)``)."""
    half = head_dim // 2
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(ang), jnp.sin(ang)
    s = dict(scaling)
    if s.get("type", s.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {s}: only yarn is implemented")
    freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, s), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    m = yarn_mscale(s["factor"], s.get("mscale", 1)) / yarn_mscale(
        s["factor"], s.get("mscale_all_dim", 0))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (x0, x1) = (even, odd) channels.  x: (..., S, H, D)."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # cos/sin: (..., S, D/2) -> broadcast over the head axis.
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(dt)
