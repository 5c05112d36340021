"""Model assembly: embedding -> patterned block stack -> head.

Layers are grouped into the minimal repeating *unit* of the config's
block pattern (e.g. jamba's 8-layer attn/mamba/MoE cycle) and scanned
with ``jax.lax.scan`` over unit repetitions, keeping the lowered HLO
small and compile times bounded even for 60-layer MoE models.  A
non-periodic prefix (deepseek's first dense layer) is applied eagerly.

Three entry points:

* ``init(key, cfg)``                      -> params
* ``forward(params, cfg, batch)``         -> logits, aux  (training)
* ``prefill(params, cfg, batch, max_len)``-> logits, cache
* ``decode_step(params, cfg, tok, cache, pos)`` -> logits, cache
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .attention import GQA, MLA
from .common import (ModelConfig, PyTree, act_fn, dense, init_norm,
                     make_dense, norm, rope_tables)
from .moe import MoE
from .ssm import Mamba
from .xlstm import MLSTM, SLSTM

__all__ = ["init", "forward", "prefill", "decode_step", "init_cache",
           "unit_period", "count_params", "model_flops"]

_MIXERS = {"attn": None, "mamba": Mamba, "mlstm": MLSTM, "slstm": SLSTM}


# ---------------------------------------------------------------------------
# Layer plumbing
# ---------------------------------------------------------------------------

def _attn_cls(cfg: ModelConfig):
    return MLA if cfg.attn_type == "mla" else GQA


def _has_ff(cfg: ModelConfig, i: int) -> bool:
    kind = cfg.layer_kind(i)
    return kind in ("attn", "mamba") and (cfg.d_ff > 0 or cfg.is_moe_layer(i))


def n_moe_layers(cfg: ModelConfig) -> int:
    """Mixture-of-experts layers one pass through the model runs."""
    return sum(_has_ff(cfg, i) and cfg.is_moe_layer(i)
               for i in range(cfg.n_layers))


def _layer_sig(cfg: ModelConfig, i: int) -> tuple:
    return (cfg.layer_kind(i), cfg.is_moe_layer(i), _has_ff(cfg, i))


def unit_period(cfg: ModelConfig) -> tuple[int, int]:
    """(prefix_len, period): layers [prefix:] repeat with ``period``."""
    n = cfg.n_layers
    prefix = cfg.first_dense_layers
    sigs = [_layer_sig(cfg, i) for i in range(prefix, n)]
    m = len(sigs)
    for p in range(1, m + 1):
        if m % p == 0 and all(sigs[i] == sigs[i % p] for i in range(m)):
            return prefix, p
    return prefix, m


def _init_mlp(key, cfg: ModelConfig) -> PyTree:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(ff * 2 * cfg.n_layers)
    p = {"w_up": make_dense(ks[1], d, ff, scale=s_in),
         "w_down": make_dense(ks[2], ff, d, scale=s_out)}
    if cfg.act == "swiglu":
        p["w_gate"] = make_dense(ks[0], d, ff, scale=s_in)
    return p


def _mlp(p: PyTree, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.act == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x)
    else:
        h = jax.nn.gelu(dense(p["w_up"], x))
    return dense(p["w_down"], h)


def _init_layer(key, cfg: ModelConfig, i: int) -> PyTree:
    kind = cfg.layer_kind(i)
    ks = iter(jax.random.split(key, 4))
    p: PyTree = {"norm1": init_norm(cfg.d_model, cfg.norm)}
    if kind == "attn":
        p["mixer"] = _attn_cls(cfg).init(next(ks), cfg)
    else:
        p["mixer"] = _MIXERS[kind].init(next(ks), cfg)
    if _has_ff(cfg, i):
        p["norm2"] = init_norm(cfg.d_model, cfg.norm)
        if cfg.is_moe_layer(i):
            p["moe"] = MoE.init(next(ks), cfg)
        else:
            p["mlp"] = _init_mlp(next(ks), cfg)
    return p


def _zero_aux() -> dict:
    return {"moe_lb_loss": jnp.float32(0), "moe_z_loss": jnp.float32(0),
            "moe_drop_frac": jnp.float32(0)}


def _apply_layer(p: PyTree, cfg: ModelConfig, i: int, x: jnp.ndarray,
                 cos, sin, impl: str) -> tuple[jnp.ndarray, dict, PyTree]:
    """Full-sequence layer.  Returns (x, aux, state) — state for prefill."""
    kind = cfg.layer_kind(i)
    aux = _zero_aux()
    h = norm(p["norm1"], x, cfg.norm)
    state = None
    if kind == "attn":
        y = _attn_cls(cfg).fwd(p["mixer"], cfg, h, cos, sin, impl=impl)
    elif kind == "mamba":
        y = Mamba.fwd(p["mixer"], cfg, h)
    elif kind == "mlstm":
        y = MLSTM.fwd(p["mixer"], cfg, h)
    else:
        y = SLSTM.fwd(p["mixer"], cfg, h)
    x = x + y
    if "norm2" in p:
        h = norm(p["norm2"], x, cfg.norm)
        if "moe" in p:
            y, aux = MoE.fwd(p["moe"], cfg, h)
        else:
            y = _mlp(p["mlp"], cfg, h)
        x = x + y
    return x, aux, state


# ---------------------------------------------------------------------------
# Model init / forward
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig) -> PyTree:
    prefix, period = unit_period(cfg)
    reps = (cfg.n_layers - prefix) // period
    k_embed, k_head, k_prefix, k_stack = jax.random.split(key, 4)
    params: PyTree = {"final_norm": init_norm(cfg.d_model, cfg.norm)}
    if cfg.input_mode == "tokens":
        params["embed"] = {
            "w": jax.random.normal(k_embed, (cfg.vocab, cfg.d_model)) * 0.02}
    else:  # stub modality frontend: inputs arrive as embeddings
        params["embed"] = {
            "proj": make_dense(k_embed, cfg.d_model, cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = make_dense(
            k_head, cfg.d_model, cfg.vocab, scale=1.0 / math.sqrt(cfg.d_model))
    params["prefix"] = [
        _init_layer(k, cfg, i) for i, k in enumerate(
            jax.random.split(k_prefix, max(prefix, 1))[:prefix])]
    # Stacked unit params: leaves get a leading (reps,) axis.
    stack = []
    pos_keys = jax.random.split(k_stack, period)
    for u in range(period):
        layer_idx = prefix + u
        rep_keys = jax.random.split(pos_keys[u], reps)
        stack.append(jax.vmap(lambda k: _init_layer(k, cfg, layer_idx))(
            rep_keys))
    params["stack"] = stack
    return params


def _embed(params: PyTree, cfg: ModelConfig, batch) -> jnp.ndarray:
    dt = cfg.compute_dtype
    if cfg.input_mode == "tokens":
        x = params["embed"]["w"].astype(dt)[batch]
    else:
        x = dense(params["embed"]["proj"], batch.astype(dt))
    return x


def _head(params: PyTree, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    from repro.dist.context import constrain
    x = norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["w"].astype(x.dtype).T
    else:
        logits = dense(params["lm_head"], x)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = constrain(logits, ("dp",) + (None,) * (logits.ndim - 2) +
                       ("tp",))
    return logits


def _rope_for(cfg: ModelConfig, positions: jnp.ndarray):
    dim = cfg.qk_rope_head_dim if cfg.attn_type == "mla" else cfg.head_dim
    return rope_tables(positions, dim, cfg.rope_theta, cfg.rope_scaling)


def forward(params: PyTree, cfg: ModelConfig, batch, *,
            remat: bool = True, impl: str = "xla"
            ) -> tuple[jnp.ndarray, dict]:
    """Training/eval forward.  batch: (B,S) int tokens or (B,S,d) embeds."""
    from repro.dist.context import constrain
    prefix, period = unit_period(cfg)
    x = _embed(params, cfg, batch)
    x = constrain(x, ("dp", None, None))
    S = x.shape[1]
    cos, sin = _rope_for(cfg, jnp.arange(S))
    aux_tot = _zero_aux()

    for i, lp in enumerate(params["prefix"]):
        x, aux, _ = _apply_layer(lp, cfg, i, x, cos, sin, impl)
        aux_tot = jax.tree.map(jnp.add, aux_tot, aux)

    def unit_body(x, unit_params):
        aux_u = _zero_aux()
        for u in range(period):
            x, aux, _ = _apply_layer(unit_params[u], cfg, prefix + u,
                                     x, cos, sin, impl)
            x = constrain(x, ("dp", None, None))
            aux_u = jax.tree.map(jnp.add, aux_u, aux)
        return x, aux_u

    body = jax.checkpoint(unit_body) if remat else unit_body

    def scan_body(carry, unit_params):
        x = carry
        x, aux_u = body(x, unit_params)
        return x, aux_u

    if (cfg.n_layers - prefix) > 0:
        x, aux_stack = jax.lax.scan(scan_body, x, tuple(params["stack"]))
        aux_tot = jax.tree.map(lambda a, b: a + jnp.sum(b), aux_tot,
                               aux_stack)
    logits = _head(params, cfg, x)
    return logits, aux_tot


def forward_features(params: PyTree, cfg: ModelConfig, batch, *,
                     remat: bool = True, impl: str = "xla",
                     unroll: bool = False) -> tuple[jnp.ndarray, dict]:
    """Like :func:`forward` but stops before the LM head, returning the
    final-norm hidden states — lets the loss head run chunked so the
    (tokens, vocab) logits tensor is never materialised at once."""
    prefix, period = unit_period(cfg)
    # Temporarily reuse forward's machinery by replicating its body
    # minus the head.
    from repro.dist.context import constrain
    x = _embed(params, cfg, batch)
    x = constrain(x, ("dp", None, None))
    S = x.shape[1]
    cos, sin = _rope_for(cfg, jnp.arange(S))
    aux_tot = _zero_aux()
    for i, lp in enumerate(params["prefix"]):
        x, aux, _ = _apply_layer(lp, cfg, i, x, cos, sin, impl)
        aux_tot = jax.tree.map(jnp.add, aux_tot, aux)

    def unit_body(x, unit_params):
        aux_u = _zero_aux()
        for u in range(period):
            x, aux, _ = _apply_layer(unit_params[u], cfg, prefix + u,
                                     x, cos, sin, impl)
            x = constrain(x, ("dp", None, None))
            aux_u = jax.tree.map(jnp.add, aux_u, aux)
        return x, aux_u

    body = jax.checkpoint(unit_body) if remat else unit_body

    def scan_body(carry, unit_params):
        return body(carry, unit_params)

    reps = (cfg.n_layers - prefix) // period if period else 0
    if reps > 0 and unroll:
        # python-loop lowering: every layer's ops appear in the HLO
        # (used by the roofline depth-extrapolation validator, where
        # cost_analysis must see each unit's cost)
        for r in range(reps):
            up = tuple(jax.tree.map(lambda a, r=r: a[r], st)
                       for st in params["stack"])
            x, aux_u = body(x, up)
            aux_tot = jax.tree.map(jnp.add, aux_tot, aux_u)
    elif reps > 0:
        x, aux_stack = jax.lax.scan(scan_body, x, tuple(params["stack"]))
        aux_tot = jax.tree.map(lambda a, b: a + jnp.sum(b), aux_tot,
                               aux_stack)
    x = norm(params["final_norm"], x, cfg.norm)
    return x, aux_tot


def head_matrix(params: PyTree, cfg: ModelConfig) -> jnp.ndarray:
    """(d, vocab) projection used by the chunked loss."""
    if cfg.tie_embeddings:
        return params["embed"]["w"].T
    return params["lm_head"]["w"]


def prefill_logits(params: PyTree, cfg: ModelConfig, batch, *,
                   impl: str = "xla") -> jnp.ndarray:
    """Serving prefill: run the prompt through the stack and return the
    LAST position's logits only — (B, vocab).

    The (B, S, vocab) logits tensor never exists: this is what a
    serving engine actually needs before decode starts, and it removes
    the dominant all-gather + 37 GiB/device buffer the naive
    full-logits prefill shows in the dry-run (EXPERIMENTS.md §Perf).
    """
    x, _ = forward_features(params, cfg, batch, remat=False, impl=impl)
    last = x[:, -1, :]                      # features are already normed
    logits = last @ head_matrix(params, cfg).astype(last.dtype)
    if not cfg.tie_embeddings and "b" in params.get("lm_head", {}):
        logits = logits + params["lm_head"]["b"].astype(logits.dtype)
    return logits


# ---------------------------------------------------------------------------
# KV-cache init / prefill / decode
# ---------------------------------------------------------------------------

def _mixer_cache(cfg: ModelConfig, i: int, batch: int, max_len: int,
                 dtype) -> PyTree:
    kind = cfg.layer_kind(i)
    if kind == "attn":
        return _attn_cls(cfg).init_cache(cfg, batch, max_len, dtype)
    return _MIXERS[kind].init_cache(cfg, batch, max_len, dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> PyTree:
    prefix, period = unit_period(cfg)
    reps = (cfg.n_layers - prefix) // period
    cache: PyTree = {
        "prefix": [
            _mixer_cache(cfg, i, batch, max_len, dtype)
            for i in range(prefix)],
        "stack": [],
    }
    for u in range(period):
        one = _mixer_cache(cfg, prefix + u, batch, max_len, dtype)
        cache["stack"].append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape), one))
    return cache


def _new_cache(cfg: ModelConfig, i: int, cache: PyTree, out: PyTree,
               pos, axis: int) -> PyTree:
    """Layer ``i``'s cache after a call whose mixer returned ``out``.
    An attention layer's cache is positional and ``out`` holds the new
    token's rows: each leaf's row is written at ``pos % slots`` on the
    position ``axis``, once.  A recurrent state is replaced by ``out``."""
    if cfg.layer_kind(i) != "attn":
        return out
    return jax.tree.map(
        lambda c, r: jax.lax.dynamic_update_slice_in_dim(
            c, r, pos % c.shape[axis], axis=axis), cache, out)


def _decode_layer(p: PyTree, cfg: ModelConfig, i: int, x: jnp.ndarray,
                  c: PyTree, pos) -> tuple[jnp.ndarray, PyTree]:
    """One layer at one position: the residual stream and what its
    mixer's ``decode`` returned (rows or a new state, :func:`_new_cache`).
    ``c`` is only read."""
    kind = cfg.layer_kind(i)
    h = norm(p["norm1"], x, cfg.norm)
    cls = _attn_cls(cfg) if kind == "attn" else _MIXERS[kind]
    y, c = cls.decode(p["mixer"], cfg, h, c, pos)
    x = x + y
    if "norm2" in p:
        h = norm(p["norm2"], x, cfg.norm)
        if "moe" in p:
            y, _ = MoE.fwd(p["moe"], cfg, h)
        else:
            y = _mlp(p["mlp"], cfg, h)
        x = x + y
    return x, c


def _experts_apart(stack: list) -> tuple[list, list]:
    """The stacked units without their routed experts' matrices, and
    those matrices (None for a unit without experts).

    A layer that holds a share of the experts runs each held expert in
    a branch (``MoE._gathered_ffn``); a branch's operand sliced out of
    the layer stack is copied whole, every held expert of the layer.
    So the share's layers get the stacks over all layers and their
    layer index (:func:`_with_experts`), and each branch reads one
    expert's matrices by (layer, expert)."""
    rest, experts = [], []
    for unit in stack:
        moe = dict(unit.get("moe", {}))
        experts.append(moe.pop("experts", None))
        rest.append({**unit, "moe": moe} if "moe" in unit else unit)
    return rest, experts


def _with_experts(unit: PyTree, experts, layer) -> PyTree:
    if experts is None:
        return unit
    return {**unit, "moe": {**unit["moe"],
                            "experts": {**experts, "layer": layer}}}


def decode_step(params: PyTree, cfg: ModelConfig, tok, cache: PyTree,
                pos, *, unroll: bool = False
                ) -> tuple[jnp.ndarray, PyTree]:
    """One autoregressive step.  tok: (B,) int32 or (B,1,d) embeds;
    pos: scalar int32 count of tokens already in the cache.

    ``unroll=True`` replaces the layer scan with a python loop: the
    per-token HLO is tiny, and unrolling lets resident (serve-mode)
    weights be consumed in place instead of being copied into the
    scan's stacked layout — see EXPERIMENTS.md §Perf.

    Either way the layers read the cache and return only their new
    rows (a recurrent layer its new state), and each cache leaf is
    written once after the layers, one row per layer: a layer's cache
    passed through the scan's outputs would be copied whole each call."""
    from repro.dist.context import constrain
    prefix, period = unit_period(cfg)
    if cfg.input_mode == "tokens":
        x = _embed(params, cfg, tok[:, None])
    else:
        x = _embed(params, cfg, tok)
    x = constrain(x, ("dp", None, None))
    pos = jnp.asarray(pos, jnp.int32)
    new_prefix = []
    for i, lp in enumerate(params["prefix"]):
        x, out = _decode_layer(lp, cfg, i, x, cache["prefix"][i], pos)
        new_prefix.append(_new_cache(cfg, i, cache["prefix"][i], out, pos,
                                     axis=1))

    reps = (cfg.n_layers - prefix) // period if period else 0
    stack, experts = params["stack"], None
    if cfg.expert_share:
        stack, experts = _experts_apart(stack)

    if unroll and reps:
        new_stack = []
        per_rep = []
        for r in range(reps):
            rep_out = []
            for u in range(period):
                up = jax.tree.map(lambda a, r=r: a[r], stack[u])
                if cfg.expert_share:
                    up = _with_experts(up, experts[u], r)
                uc = jax.tree.map(lambda a, r=r: a[r], cache["stack"][u])
                x, out = _decode_layer(up, cfg, prefix + u, x, uc, pos)
                x = constrain(x, ("dp", None, None))
                rep_out.append(out)
            per_rep.append(rep_out)
        for u in range(period):
            outs = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                *[per_rep[r][u] for r in range(reps)])
            new_stack.append(_new_cache(cfg, prefix + u, cache["stack"][u],
                                        outs, pos, axis=2))
        logits = _head(params, cfg, x)
        return logits[:, 0], {"prefix": new_prefix, "stack": new_stack}

    def scan_body(x, inp):
        unit_params, unit_cache = inp[:2]
        if cfg.expert_share:
            unit_params = [_with_experts(up, ex, inp[2])
                           for up, ex in zip(unit_params, experts)]
        outs = []
        for u in range(period):
            x, out = _decode_layer(unit_params[u], cfg, prefix + u, x,
                                   unit_cache[u], pos)
            x = constrain(x, ("dp", None, None))
            outs.append(out)
        return x, tuple(outs)

    new_stack = []
    if (cfg.n_layers - prefix) > 0:
        xs = (tuple(stack), tuple(cache["stack"]))
        if cfg.expert_share:
            xs += (jnp.arange(reps),)
        x, outs = jax.lax.scan(scan_body, x, xs)
        new_stack = [_new_cache(cfg, prefix + u, cache["stack"][u], outs[u],
                                pos, axis=2) for u in range(period)]
    logits = _head(params, cfg, x)
    return logits[:, 0], {"prefix": new_prefix, "stack": new_stack}


def prefill(params: PyTree, cfg: ModelConfig, batch, max_len: int,
            *, impl: str = "xla") -> tuple[jnp.ndarray, PyTree]:
    """Run the prompt through the model, returning (last-token logits,
    cache filled for positions [0, S)).

    Implemented as forward + per-layer state extraction; attention
    layers re-project K/V into the cache layout (cheap relative to the
    attention itself), recurrent layers return their final states.
    """
    # For simplicity and correctness-first: replay tokens through
    # decode_step via lax.scan when S is small, else use the fused path.
    if cfg.input_mode == "tokens":
        B, S = batch.shape
    else:
        B, S = batch.shape[:2]
    cache = init_cache(cfg, B, max_len)

    def step(carry, s):
        cache = carry
        tok = jax.lax.dynamic_index_in_dim(batch, s, axis=1, keepdims=False)
        if cfg.input_mode != "tokens":
            tok = tok[:, None]
        logits, cache = decode_step(params, cfg, tok, cache, s)
        return cache, logits

    cache, logits = jax.lax.scan(step, cache, jnp.arange(S))
    return logits[-1], cache


def count_params(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def model_flops(cfg: ModelConfig, n_params_active: int, n_tokens: int) -> float:
    """MODEL_FLOPS = 6 * N_active * D (the roofline 'useful work' term)."""
    return 6.0 * n_params_active * n_tokens
