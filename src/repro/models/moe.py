"""Mixture-of-Experts with capacity-based, sort-free static dispatch.

Design constraints:

* static shapes only (jit/pjit friendly): per-expert buffers of
  ``capacity`` slots, overflow tokens dropped (standard Switch/GShard
  semantics, capacity_factor controls the drop rate),
* no O(T*E*C) one-hot tensors: slot indices are computed with a sort
  over token-expert assignments and a segment-relative ranking, then
  tokens are gathered into an (E, C, d) buffer — the Megablocks-style
  grouped-GEMM layout that XLA SPMD shards cleanly,
* experts are sharded over the "model" mesh axis when ``E`` divides it
  (expert parallelism, e.g. deepseek 160/16); otherwise each expert's
  ``d_ff`` is sharded (tensor parallelism inside experts, e.g. mixtral
  8 experts on a 16-way axis),
* router computed in f32 with load-balance + z losses (returned as
  aux so the train step can weight them).

Without a mesh, the input's static shape picks one of two local paths
(:meth:`MoE.local_path`).  The grouped buffer multiplies all ``E``
experts' weights, whatever the routing; when the ``T·K`` token-expert
assignments are fewer than ``E`` (decode: one token, ``K < E``) the
gathered path instead reads each routed expert's matrices by its
scalar id, so only ``T·K`` experts' weights are streamed, and drops
nothing.  On that range an expert receives at most ``T`` tokens; where
``T`` is within the grouped buffer's minimum capacity of 8 (always, for
``E <= 9K``: Mixtral's 8 experts, top-2, give ``T <= 3``) the grouped
path drops nothing either, and both compute the same sums.
Training-size inputs and the distributed paths stay grouped.

Routing follows the configuration: a softmax over the router's ``E``
experts, optionally limited to the ``topk_group`` best of ``n_group``
groups (a group scores its best expert; DeepSeek-V2's
``group_limited_greedy``), the top ``K`` kept, renormalised or not
(``norm_topk_prob``) and times ``routed_scaling_factor``.

A layer may hold only a share of the experts (``experts_held`` from
``expert_offset``; expert parallelism with this chip's exchange left
out): it routes over all ``E`` and adds only its held experts' part.
An assignment to an absent expert adds nothing and reads no weights:
the gathered path runs each held assignment in a ``lax.cond`` branch,
and the grouped buffer has rows for the held experts only.  The local
path compares ``T·K`` with the held count.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import ModelConfig, PyTree, make_dense

__all__ = ["MoE"]


def _expert_ffn(p: PyTree, x: jnp.ndarray, act: str) -> jnp.ndarray:
    """Grouped SwiGLU/GELU ffn over (E, C, d) buffers."""
    wg, wu, wd = (p["w_gate"].astype(x.dtype), p["w_up"].astype(x.dtype),
                  p["w_down"].astype(x.dtype))
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg)) * \
            jnp.einsum("ecd,edf->ecf", x, wu)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, wg))
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _expert_weights(p: PyTree, e) -> PyTree:
    """Expert ``e``'s matrices, a dynamic slice of each stack; of layer
    ``p["layer"]`` where the stacks hold every layer's experts
    (``transformer._experts_apart``)."""
    names = ("w_gate", "w_up", "w_down")
    if "layer" not in p:
        return {n: jax.lax.dynamic_index_in_dim(p[n], e) for n in names}
    return {n: jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(p[n], p["layer"], keepdims=False), e)
        for n in names}


def _gathered_ffn(p: PyTree, xt: jnp.ndarray, expert_ids: jnp.ndarray,
                  gates: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Routed experts of each token, read one by one: xt (T, d),
    expert_ids and gates (T, K) -> (T, d).

    Each expert's matrices are a dynamic slice at its scalar id, which
    XLA fuses into the matmul that reads it, so only the T·K routed
    experts' weights are streamed (a gather along the expert axis
    would copy them first).  The loop is unrolled: T·K < held bounds
    it.  Outputs are combined as the grouped path does: each in
    ``xt.dtype``, times its gate in ``xt.dtype``, summed token-major in
    k order.  Where the layer holds a share of the experts, each
    assignment runs in a ``lax.cond`` on whether its expert is held:
    an absent one adds zeros and reads nothing."""
    T, K = expert_ids.shape
    g = gates.astype(xt.dtype)

    def routed(p, x, e, gate):
        y = _expert_ffn(_expert_weights(p, e), x[None], cfg.act)[0, 0]
        return y * gate

    rows = []
    for t in range(T):
        row = None
        for k in range(K):
            e = expert_ids[t, k]
            if cfg.expert_share:
                e = e - cfg.expert_offset
                y = jax.lax.cond(
                    (e >= 0) & (e < cfg.n_held), routed,
                    lambda p, x, e, gate: jnp.zeros(x.shape[1:], x.dtype),
                    p, xt[t:t + 1], e, g[t, k])
            else:
                y = routed(p, xt[t:t + 1], e, g[t, k])
            row = y if row is None else row + y
        rows.append(row)
    return jnp.stack(rows)


class MoE:
    @staticmethod
    def init(key, cfg: ModelConfig) -> PyTree:
        """The router over all ``n_experts``; the held experts' stacks."""
        d, E, ff = cfg.d_model, cfg.n_held, cfg.moe_d_ff
        ks = iter(jax.random.split(key, 8))
        s_in = 1.0 / math.sqrt(d)
        s_out = 1.0 / math.sqrt(ff * 2 * cfg.n_layers)
        p = {
            "router": make_dense(next(ks), d, cfg.n_experts, scale=s_in),
            "experts": {
                "w_gate": jax.random.normal(next(ks), (E, d, ff)) * s_in,
                "w_up": jax.random.normal(next(ks), (E, d, ff)) * s_in,
                "w_down": jax.random.normal(next(ks), (E, ff, d)) * s_out,
            },
        }
        if cfg.n_shared_experts:
            ff_sh = ff * cfg.n_shared_experts
            p["shared"] = {
                "w_gate": make_dense(next(ks), d, ff_sh, scale=s_in),
                "w_up": make_dense(next(ks), d, ff_sh, scale=s_in),
                "w_down": make_dense(next(ks), ff_sh, d, scale=s_out),
            }
        return p

    @staticmethod
    def capacity(cfg: ModelConfig, n_tokens: int) -> int:
        c = int(math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor
                          / cfg.n_experts))
        return max(8, -(-c // 8) * 8)  # pad to multiple of 8

    @staticmethod
    def local_path(cfg: ModelConfig, n_tokens: int) -> str:
        """The local path ``n_tokens`` tokens take: ``"gathered"`` while
        their ``T·K`` assignments name fewer expert matrices than the
        grouped buffer's held experts, else ``"grouped"``."""
        if n_tokens * cfg.top_k < cfg.n_held:
            return "gathered"
        return "grouped"

    @staticmethod
    def fwd(p: PyTree, cfg: ModelConfig, x: jnp.ndarray
            ) -> tuple[jnp.ndarray, dict]:
        """x: (B, S, d) -> (y, aux_losses).

        Dispatches to the distributed path when a tensor/expert-parallel
        mesh axis is installed (see :mod:`repro.dist.context`):

        * ``E % tp == 0``: expert parallelism — local routing, fixed-
          capacity all_to_all to expert shards, grouped GEMM, reverse
          all_to_all (the Switch/GShard schedule, explicit via
          shard_map so SPMD can never replicate token buffers),
        * otherwise: experts replicated over tokens, each shard computes
          a d_ff slice of every expert and psums (tensor parallelism
          inside experts).
        """
        from repro.dist import context as dctx
        tp = dctx.tp_size()
        if tp > 1 and dctx.mesh() is not None:
            if cfg.n_experts % tp == 0:
                return MoE._fwd_ep(p, cfg, x)
            return MoE._fwd_tp(p, cfg, x)
        return MoE._fwd_local(p, cfg, x)

    @staticmethod
    def _fwd_local(p: PyTree, cfg: ModelConfig, x: jnp.ndarray
                   ) -> tuple[jnp.ndarray, dict]:
        B, S, d = x.shape
        K, off = cfg.top_k, cfg.expert_offset
        T = B * S
        xt = x.reshape(T, d)

        if MoE.local_path(cfg, T) == "gathered":
            logits, probs, gate_vals, flat_e = MoE._route(p, cfg, xt)
            with jax.named_scope("moe.held_experts"):
                y = _gathered_ffn(p["experts"], xt, flat_e.reshape(T, K),
                                  gate_vals, cfg)
            keep = jnp.ones(flat_e.shape, bool)
        else:
            C = MoE.capacity(cfg, T)
            logits, probs, gate_vals, flat_e, ranks, keep = \
                MoE._route_local(p, cfg, xt, C)
            with jax.named_scope("moe.held_experts"):
                experts = p["experts"]
                if "layer" in experts:
                    experts = {n: a[experts["layer"]]
                               for n, a in experts.items() if n != "layer"}
                local = flat_e - off
                # an absent expert's assignments are left out as dropped
                use = keep & (local >= 0) & (local < cfg.n_held)
                slot = jnp.where(use, local * C + ranks, 0)    # (T*K,)
                token_idx = jnp.repeat(jnp.arange(T), K)
                # Scatter tokens into the (held*C, d) buffer (left out
                # -> slot 0 masked).
                buf = jnp.zeros((cfg.n_held * C, d), x.dtype)
                contrib = jnp.where(use[:, None], xt[token_idx], 0.0)
                buf = buf.at[slot].add(contrib, mode="drop")
                buf = buf.reshape(cfg.n_held, C, d)

                y_buf = _expert_ffn(experts, buf, cfg.act)      # (held, C, d)

                # Combine: gather each used assignment's output and
                # weight by gate.
                y_flat = y_buf.reshape(cfg.n_held * C, d)[slot]  # (T*K, d)
                w = jnp.where(use, gate_vals.reshape(-1), 0.0).astype(x.dtype)
                y = jnp.zeros((T, d), x.dtype).at[token_idx].add(
                    y_flat * w[:, None])

        if "shared" in p:
            with jax.named_scope("moe.shared"):
                y = y + MoE._shared_tp(p, cfg, xt, None)
        aux = MoE._aux_of(cfg, logits, probs, flat_e, keep, ())
        return y.reshape(B, S, d), aux

    # ------------------------------------------------------------------
    # Distributed paths (explicit shard_map — SPMD alone mis-shards the
    # dispatch scatter and replicates token buffers).
    # ------------------------------------------------------------------

    @staticmethod
    def _route(p, cfg, xt):
        """Router in f32: logits and probs (t, E), the top-k gates
        (t, K) and their expert ids, flattened (t*K,)."""
        logits = xt.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        scores = probs
        if cfg.n_group:
            with jax.named_scope("moe.route_group_limited"):
                t, G = probs.shape[0], cfg.n_group
                grouped = probs.reshape(t, G, cfg.n_experts // G)
                _, best = jax.lax.top_k(jnp.max(grouped, -1), cfg.topk_group)
                kept = jnp.sum(jax.nn.one_hot(best, G, dtype=jnp.int32), 1)
                scores = jnp.where(kept[:, :, None] > 0, grouped,
                                   0.0).reshape(t, -1)
        gate_vals, expert_ids = jax.lax.top_k(scores, cfg.top_k)
        if cfg.norm_topk_prob:
            gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
        if cfg.routed_scaling_factor != 1.0:
            gate_vals = gate_vals * cfg.routed_scaling_factor
        return logits, probs, gate_vals, expert_ids.reshape(-1)

    @staticmethod
    def _route_local(p, cfg, xt, capacity):
        """Shared routing: top-k, capacity ranks.  xt: (t, d) local."""
        E, K = cfg.n_experts, cfg.top_k
        t = xt.shape[0]
        logits, probs, gate_vals, flat_e = MoE._route(p, cfg, xt)
        # Priority: earlier tokens win capacity (GShard semantics); the
        # rank within an expert's group is index - start(expert).
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.bincount(sorted_e, length=E)
        starts = jnp.cumsum(counts) - counts
        ranks_sorted = jnp.arange(t * K) - starts[sorted_e]
        ranks = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)
        keep = ranks < capacity
        return logits, probs, gate_vals, flat_e, ranks, keep

    @staticmethod
    def _aux_of(cfg, logits, probs, flat_e, keep, axes):
        E, K = cfg.n_experts, cfg.top_k
        t = probs.shape[0]

        def mean_over(v):
            if axes:
                return jax.lax.pmean(v, axes)
            return v

        me = mean_over(jnp.mean(probs, axis=0))
        frac = mean_over(
            jnp.bincount(flat_e, length=E).astype(jnp.float32) / (t * K))
        lb = E * jnp.sum(frac * me)
        z = mean_over(jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))))
        drop = mean_over(1.0 - jnp.mean(keep.astype(jnp.float32)))
        return {"moe_lb_loss": lb, "moe_z_loss": z, "moe_drop_frac": drop}

    @staticmethod
    def _shared_tp(p, cfg, xt, tp_axis):
        """Shared experts with d_ff tensor-parallel over ``tp_axis``
        (``None``: unsharded, the local path)."""
        if "shared" not in p:
            return 0.0
        sh = p["shared"]
        wg = sh["w_gate"]["w"].astype(xt.dtype)
        wu = sh["w_up"]["w"].astype(xt.dtype)
        wd = sh["w_down"]["w"].astype(xt.dtype)
        if cfg.act == "swiglu":
            h = jax.nn.silu(xt @ wg) * (xt @ wu)
        else:
            h = jax.nn.gelu(xt @ wg)
        y = h @ wd
        return jax.lax.psum(y, tp_axis) if tp_axis else y

    @staticmethod
    def _whole_bank(cfg: ModelConfig) -> None:
        """The distributed paths shard the whole bank themselves."""
        if cfg.expert_share:
            raise NotImplementedError(
                f"{cfg.name}: a layer holding {cfg.n_held} of "
                f"{cfg.n_experts} experts runs on one chip, without a "
                f"mesh; expert parallelism over such layers is not "
                f"implemented")

    @staticmethod
    def _fwd_ep(p: PyTree, cfg: ModelConfig, x: jnp.ndarray
                ) -> tuple[jnp.ndarray, dict]:
        """Expert parallelism: tokens split over (dp, tp); fixed-capacity
        all_to_all dispatch to expert shards; reverse combine."""
        MoE._whole_bank(cfg)
        from jax.sharding import PartitionSpec as P
        from repro.dist import context as dctx

        mesh = dctx.mesh()
        dp_ax, tp_ax = dctx.activation_axes()
        dp_axes = tuple(dp_ax) if isinstance(dp_ax, (tuple, list)) else (
            (dp_ax,) if dp_ax else ())
        B, S, d = x.shape
        E, K = cfg.n_experts, cfg.top_k
        m = dctx.tp_size()
        E_loc = E // m
        T = B * S

        # Token sharding must stay aligned with the outer (B, S, d)
        # activation layout or the backward respec replicates the full
        # batch: batch over the DP axes (when divisible) and *sequence*
        # over the model axis (sequence-parallel dispatch).  Remaining
        # replication (tiny decode batches) is correct — each source
        # shard combines only its own slots — at the cost of duplicate
        # routing compute.
        b_axes: tuple = ()
        n_b = 1
        for a in dp_axes:
            sz = mesh.shape[a]
            if B % (n_b * sz) == 0:
                b_axes += (a,)
                n_b *= sz
        s_ax = tp_ax if S % m == 0 else None
        n_tok_shards = n_b * (m if s_ax else 1)
        t_loc = T // n_tok_shards
        c_se = max(4, -(-int(t_loc * K * cfg.capacity_factor / E) // 4) * 4)

        def inner(xb, router_w, wg, wu, wd, shared):
            xt = xb.reshape(-1, d)
            pl = {"router": {"w": router_w},
                  "shared": shared} if shared is not None else {
                      "router": {"w": router_w}}
            logits, probs, gates, flat_e, ranks, keep = MoE._route_local(
                pl, cfg, xt, c_se)
            t = xt.shape[0]
            dest = flat_e // E_loc
            eslot = flat_e % E_loc
            slot = dest * (E_loc * c_se) + eslot * c_se + \
                jnp.where(keep, ranks, 0)
            token_idx = jnp.repeat(jnp.arange(t), K)
            contrib = jnp.where(keep[:, None], xt[token_idx], 0.0)
            send = jnp.zeros((m * E_loc * c_se, d), xt.dtype)
            send = send.at[slot].add(contrib, mode="drop")
            send = send.reshape(m, E_loc * c_se, d)
            recv = jax.lax.all_to_all(send, tp_ax, split_axis=0,
                                      concat_axis=0, tiled=False)
            buf = recv.reshape(m, E_loc, c_se, d).transpose(1, 0, 2, 3)
            buf = buf.reshape(E_loc, m * c_se, d)
            y_buf = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd},
                                buf, cfg.act)
            back = y_buf.reshape(E_loc, m, c_se, d).transpose(1, 0, 2, 3)
            back = back.reshape(m, E_loc * c_se, d)
            ret = jax.lax.all_to_all(back, tp_ax, split_axis=0,
                                     concat_axis=0, tiled=False)
            y_flat = ret.reshape(m * E_loc * c_se, d)[slot]
            w = jnp.where(keep, gates.reshape(-1), 0.0).astype(xt.dtype)
            y = jnp.zeros((t, d), xt.dtype).at[token_idx].add(
                y_flat * w[:, None])
            y = y + MoE._shared_tp(pl, cfg, xt, None)
            aux_axes = b_axes + ((s_ax,) if s_ax else ())
            aux = MoE._aux_of(cfg, logits, probs, flat_e, keep, aux_axes)
            return y.reshape(xb.shape), aux

        shared = p.get("shared")
        shared_spec = None
        if shared is not None:
            shared_spec = jax.tree.map(lambda _: P(None, None), shared)
        tok_spec = P(b_axes if b_axes else None, s_ax, None)
        y, aux = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(tok_spec, P(None, None),
                      P(tp_ax, None, None), P(tp_ax, None, None),
                      P(tp_ax, None, None), shared_spec),
            out_specs=(tok_spec,
                       {k: P() for k in ("moe_lb_loss", "moe_z_loss",
                                         "moe_drop_frac")}),
            check_vma=False,
        )(x, p["router"]["w"], p["experts"]["w_gate"],
          p["experts"]["w_up"], p["experts"]["w_down"], shared)
        return y, aux

    @staticmethod
    def _fwd_tp(p: PyTree, cfg: ModelConfig, x: jnp.ndarray
                ) -> tuple[jnp.ndarray, dict]:
        """Experts too few to shard: replicate routing, shard every
        expert's d_ff over the model axis, psum the combined output."""
        MoE._whole_bank(cfg)
        from jax.sharding import PartitionSpec as P
        from repro.dist import context as dctx

        mesh = dctx.mesh()
        dp_ax, tp_ax = dctx.activation_axes()
        dp_axes = tuple(dp_ax) if isinstance(dp_ax, (tuple, list)) else (
            (dp_ax,) if dp_ax else ())
        B, S, d = x.shape
        E, K = cfg.n_experts, cfg.top_k
        T = B * S
        tok_axes: tuple = ()
        n_dp = 1
        for a in dp_axes:
            sz = mesh.shape[a]
            if T % (n_dp * sz) == 0:
                tok_axes += (a,)
                n_dp *= sz
        dp_axes = tok_axes
        t_loc = T // n_dp
        C = max(8, -(-int(t_loc * K * cfg.capacity_factor / E) // 8) * 8)

        def inner(xt, router_w, wg, wu, wd, shared):
            pl = {"router": {"w": router_w}}
            if shared is not None:
                pl["shared"] = shared
            logits, probs, gates, flat_e, ranks, keep = MoE._route_local(
                pl, cfg, xt, C)
            t = xt.shape[0]
            slot = flat_e * C + jnp.where(keep, ranks, 0)
            token_idx = jnp.repeat(jnp.arange(t), K)
            contrib = jnp.where(keep[:, None], xt[token_idx], 0.0)
            buf = jnp.zeros((E * C, d), xt.dtype)
            buf = buf.at[slot].add(contrib, mode="drop").reshape(E, C, d)
            y_buf = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd},
                                buf, cfg.act)
            y_flat = y_buf.reshape(E * C, d)[slot]
            w = jnp.where(keep, gates.reshape(-1), 0.0).astype(xt.dtype)
            y = jnp.zeros((t, d), xt.dtype).at[token_idx].add(
                y_flat * w[:, None])
            y = jax.lax.psum(y, tp_ax)
            y = y + MoE._shared_tp(pl, cfg, xt, tp_ax)
            aux = MoE._aux_of(cfg, logits, probs, flat_e, keep, dp_axes)
            return y, aux

        xt = x.reshape(T, d)
        shared = p.get("shared")
        shared_spec = None
        if shared is not None:
            shared_spec = {
                "w_gate": {"w": P(None, tp_ax)},
                "w_up": {"w": P(None, tp_ax)},
                "w_down": {"w": P(tp_ax, None)},
            }
        y, aux = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(dp_axes if dp_axes else None, None), P(None, None),
                      P(None, None, tp_ax), P(None, None, tp_ax),
                      P(None, tp_ax, None), shared_spec),
            out_specs=(P(dp_axes if dp_axes else None, None),
                       {k: P() for k in ("moe_lb_loss", "moe_z_loss",
                                         "moe_drop_frac")}),
            check_vma=False,
        )(xt, p["router"]["w"], p["experts"]["w_gate"],
          p["experts"]["w_up"], p["experts"]["w_down"], shared)
        return y.reshape(B, S, d), aux
