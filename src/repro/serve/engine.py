"""Serving engine with symbiotic round scheduling (the paper's
technique as a first-class serving feature).

Every unit of pending work is characterised as a roofline work item:

* a **prefill chunk** (compute-bound: ~2·N FLOPs/token at intensity
  ~seq_len),
* a **decode step** (memory-bound: streams weights + KV/state at
  intensity ~batch),

and the *unmodified Algorithm 1* composes execution rounds that mix
compute-bound with memory-bound work near the hardware balance point
``R_B`` — the 2015 reordering insight independently rediscovering
chunked-prefill scheduling.

The engine actually executes (greedy decoding, CPU-sized models) in the
scheduled order, and reports per-round roofline times from the event
simulator so the ordering gain is measurable (see
``benchmarks/serving.py``).

Since PR 7 this module holds only the step loop and exact execution;
its composition pipeline lives in :mod:`repro.serve.composer`
(:class:`~repro.serve.composer.Composer`), the cache in
:mod:`repro.serve.cache`, and the cross-step incremental frontier in
:mod:`repro.serve.live` (:class:`~repro.serve.live.LiveComposition`).
``ScheduleCache`` and ``Signature`` are re-exported here.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tpu import (TpuWorkItem, decode_profile,
                            make_serving_device, prefill_profile,
                            round_time)
from repro.graph.kernel_graph import trace_arch
from repro.obs import LatencyTracker, MetricsRegistry, phase_breakdown
from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.models.moe import MoE

from .cache import ScheduleCache, Signature
from .composer import Composer
from .live import LiveComposition

__all__ = ["Request", "ServingEngine", "SchedulerPolicy",
           "ScheduleCache", "Signature", "build_dag_triples"]

#: One jitted decode step for every engine: engines over the same
#: config (replicas, repeated runs) share its compilations.  The step
#: runs where its committed inputs (the engine's parameters) live.
_decode_step = jax.jit(T.decode_step, static_argnums=(1,))


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int = 16
    # runtime state
    generated: list[int] = field(default_factory=list)
    #: ``time.perf_counter()`` when each token of ``generated`` was
    #: read back, one per token
    token_times: list[float] = field(default_factory=list)
    cache: object = None
    pos: int = 0
    done: bool = False


@dataclass
class SchedulerPolicy:
    kind: str = "symbiotic"               # fifo | symbiotic | refined
    refine_budget: int = 200
    #: local-search move set for kind="refined" (see repro.core.refine)
    neighborhood: str = "auto"
    #: Schedule the per-layer dependency graph instead of flat
    #: per-request items: each live request expands into its traced
    #: chain of layer-stage work items (repro.graph.trace_arch) and the
    #: ready-set greedy (repro.graph.greedy_order_dag) composes rounds
    #: that interleave *different* requests' stages while chains stay
    #: ordered.  The ScheduleCache participates with coarsened keys:
    #: instead of per-item layer-stage signatures (which re-key every
    #: step as kv-lens drift), the key is the multiset of per-request
    #: *chain* signatures (kind, kv bucket, stage count), so
    #: decode-heavy steady state gets warm hits on this path too
    #: (``dag_hits`` in ``ScheduleCache.stats()``).
    respect_deps: bool = False
    #: Kernelet-style slicing (repro.slice) on the respect_deps path:
    #: when set, a stage the ready-set greedy cannot pack with any
    #: frontier peer (a solo round) is cut per this
    #: :class:`repro.slice.SlicePolicy` into co-schedulable slices
    #: with exact accounting — slice profiles sum to the parent and
    #: the stage weight stream is still charged once per round.
    #: Default off.  Slicing only reshapes modelled rounds; chain
    #: tails still trigger exact execution (moved to the slice join),
    #: so generated tokens are bit-identical with or without it.
    slice_policy: object | None = None
    #: Optional stage coarsening for deep configs on the respect_deps
    #: path (see trace_arch(max_stages=...)); None = one item per
    #: layer stage.
    dag_max_stages: int | None = None
    #: objective for kind="refined": "rounds" re-rounds every candidate
    #: under the TPU round cost model (weight stream charged once per
    #: round); "event" / "round" refine the flat launch order under the
    #: corresponding core simulator, delta-evaluated via the
    #: checkpointing :class:`repro.core.refine.DeltaEvaluator` — the
    #: suffix re-simulation path that makes event-model refinement
    #: affordable on the serving hot path.  On the respect_deps path
    #: "gated" refines under the gated DAG makespan itself
    #: (:class:`repro.graph.delta.GatedDeltaEvaluator`) — the currency
    #: that actually scores dependency-aware schedules.
    refine_model: str = "rounds"
    #: Guard currency for the respect_deps/slice_policy path: "rounds"
    #: compares compositions against dep-aware arrival order under the
    #: TPU round cost model (each round charged its distinct stages'
    #: weight streams).  That currency structurally punishes slice
    #: rounds — every round touching a slice pays the full stage
    #: stream, so slicing wins that the gated dispatcher realizes
    #: (slices co-executing with decode work) are guarded away.
    #: "gated" compares gated-event makespans of the compositions'
    #: flat launch orders (:class:`repro.graph.DagEventSimulator` over
    #: the expanded slice/join edges) — the same currency
    #: ``benchmarks/slicing.py`` scores, letting serving accept
    #: compositions whose slice rounds genuinely co-execute.  Since
    #: PR 7 the gated guard is delta-evaluated per step: candidates
    #: over the same kernel set resume from the first candidate's
    #: checkpoints instead of re-simulating from scratch
    #: (:class:`repro.serve.composer.GatedGuard`; saved full-sim
    #: equivalents in ``ScheduleCache.stats()["gated_sims_saved"]``).
    #: The stale-replay drift re-validation stays in the round
    #: currency either way (it compares a replay against its own
    #: stored time, not against fifo).
    dag_guard: str = "rounds"
    #: ScheduleCache: reuse round compositions across steps whose
    #: work-item mix is equivalent (decode kv-lens bucketized).
    cache: bool = True
    kv_bucket: int = 256
    #: On a cache near-miss (exactly one request joined or left the
    #: mix since a cached step), adapt the cached composition instead
    #: of recomputing greedy + guard + refine from scratch.
    warm_start: bool = True
    #: Stale-replay re-validation: a replayed cached pattern whose
    #: modelled time drifts more than this fraction from the time
    #: recorded when the pattern was stored — or whose rounds no
    #: longer fit device capacity on actual demands — is not replayed
    #: optimistically; the engine re-validates and recomposes cold
    #: (counted as ``replay_revalidations`` in
    #: ``ScheduleCache.stats()``).  <= 0 disables (legacy optimistic
    #: replay).  ``composition="incremental"`` reuses the same knob as
    #: its drift backstop: the live composition's modelled ratio
    #: against dep-aware arrival order may drift at most this fraction
    #: from the ratio at the last cold (re)build.
    replay_drift_tol: float = 0.05
    #: Warm-start quality tracking: audit this fraction of warm hits
    #: by also recomputing the cold greedy composition and recording
    #: the modelled regret (warm time vs cold time, round cost model)
    #: in ``ScheduleCache.stats()``.  Deterministic sampling (every
    #: ``1/frac``-th warm hit).  Off by default: each audited hit
    #: pays the full cold greedy the warm start exists to skip, so
    #: only measurement runs (``benchmarks/serving.py``) opt in.
    #: **Deprecated alias** (PR 9): the sampling and regret recording
    #: now live on the online auditor
    #: (:meth:`repro.obs.audit.QualityAuditor.warm_audit`); the
    #: ``warm_regret_mean`` / ``warm_sampled`` stats keys are
    #: unchanged.  Prefer the ``audit_*`` knobs for new code.
    warm_audit_frac: float = 0.0
    #: Online quality audit (PR 9): deterministically sample this
    #: fraction of served steps and re-run the paper's Fig.-1
    #: protocol live — score the served composition against
    #: ``audit_k`` seeded random orders of the same kernel set under
    #: the step's own currency (gated makespan on traced steps, round
    #: cost model on flat steps).  Results land in the
    #: ``audit_quality_percentile{arch,kind}`` histogram; a verdict
    #: under ``audit_floor`` bumps ``audit_below_floor``.  Off by
    #: default; ``check_regression.py --audit-overhead`` caps the
    #: cost of ``audit_frac=0.05`` at 1.15x the audit-off run.
    audit_frac: float = 0.0
    #: random launch-order baselines per audited step (the paper's
    #: design-space sample; K=50 is the acceptance protocol).
    audit_k: int = 50
    #: live SLO floor on the served order's percentile rank (the
    #: paper claims "well above the 90 percentile mark").
    audit_floor: float = 90.0
    #: base seed for the audit baselines (each audited step derives a
    #: distinct deterministic seed from it).
    audit_seed: int = 0
    #: How the respect_deps path composes across steps (PR 7):
    #: "batch" recomposes every step from scratch (optionally through
    #: the ScheduleCache); "incremental" keeps the ready-set greedy's
    #: round-frontier state live across steps
    #: (:class:`repro.serve.live.LiveComposition`) — joining requests'
    #: chains are placed by Algorithm 1's own scoring into the
    #: existing composition, leaving requests' stages are retired in
    #: place, and everything else refreshes without moving.  Counters
    #: in ``ScheduleCache.stats()``: ``incremental_joins``,
    #: ``incremental_leaves``, ``frontier_rebuilds``.  Tokens are
    #: bit-identical either way (execution is exact per request); only
    #: per-step compose cost and modelled round times differ.  No
    #: effect on the flat (``respect_deps=False``) path.
    composition: str = "batch"


def build_dag_triples(cfg: ModelConfig, reqs: list[Request], *,
                      n_params: float, kv_bytes_per_token: float,
                      max_stages: int | None = None):
    """Trace live requests into per-layer work items.

    Every request expands into its traced chain of layer-stage items
    (:func:`repro.graph.trace_arch`).  Only the *tail* item of a chain
    carries its executable kind ``"prefill"``/``"decode"`` — the
    engine executes a request's forward pass exactly, as one unit —
    while interior stages carry kind ``"frag"`` and exist for round
    composition and modelled time only.  Returns ``(triples,
    traced)``; module-level so benchmark drivers can compose traced
    steps without instantiating an engine
    (``benchmarks/serving.py``'s churn workload).
    """
    spec = []
    for r in reqs:
        if r.cache is None:
            spec.append(("prefill", int(len(r.prompt))))
        else:
            spec.append(("decode", r.pos))
    traced = trace_arch(cfg, spec, n_params=n_params,
                        kv_bytes_per_token=kv_bytes_per_token,
                        max_stages=max_stages)
    triples = []
    for i, it in enumerate(traced.items):
        owner = traced.owners[i]
        r = reqs[owner]
        if i == traced.tail_of[owner]:
            kind = "prefill" if r.cache is None else "decode"
        else:
            kind = "frag"
        triples.append((it, r, kind))
    return triples, traced


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 256,
                 n_params: float | None = None,
                 policy: SchedulerPolicy | None = None,
                 device=None, metrics: MetricsRegistry | None = None,
                 trace=None, recorder=None, schedule_cache=None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.policy = policy or SchedulerPolicy()
        self.n_params = n_params or float(T.count_params(params))
        self.device = device or make_serving_device()
        self.weights_bytes = 2.0 * self.n_params  # bf16 weight stream
        self.queue: list[Request] = []
        self._round_times: list[float] = []
        #: the unified registry (PR 8): cache counters, composer
        #: guard/refine timers and the engine's own phase timers all
        #: land here; ``run()`` re-exports its snapshot.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        #: optional :class:`repro.obs.ScheduleTrace` — when set,
        #: ``step()`` records one span per executed round member on
        #: the engine's modelled-round timeline (round boundaries as
        #: instants).  Purely read-only over already-computed round
        #: times, so modelled times and generated tokens are
        #: bit-identical with and without it.
        self.trace = trace
        self._trace_t = 0.0
        #: optional :class:`repro.obs.FlightRecorder` (PR 9): the
        #: composer, live frontier and auditor emit schedule
        #: decisions, cache outcomes, rebuild reasons and audit
        #: verdicts as JSONL events.  Same null-path contract as
        #: ``trace``: tokens and modelled times are bit-identical
        #: with and without it.
        self.recorder = recorder
        #: PR 10: a pre-built :class:`ScheduleCache` may be injected so
        #: several engine replicas behind ``repro.serve.frontend`` share
        #: one pattern store (cache-aware routing then pays off across
        #: replicas).  An injected cache keeps its *own* metrics
        #: registry — its counters and the composer's guard/refine
        #: timers land there, not in this engine's registry.
        self.schedule_cache = (
            schedule_cache if schedule_cache is not None else
            ScheduleCache(kv_bucket=self.policy.kv_bucket,
                          metrics=self.metrics))
        self.composer = Composer(self.policy, self.device,
                                 self.weights_bytes,
                                 self.schedule_cache,
                                 recorder=recorder)
        self.live = (LiveComposition(self.composer)
                     if self.policy.composition == "incremental"
                     else None)
        #: per-request arrival→completion latency spans (PR 9); fed
        #: by ``submit()`` / ``step()``, exported as
        #: ``run()``-stats ``"latency"`` (p50/p95/p99 + goodput).
        self.latency = LatencyTracker(self.metrics)
        # hot-path series, resolved once (see repro.obs.profile)
        m = self.metrics
        self._steps = m.counter("engine_steps")
        self._phase = {ph: m.histogram(f"phase_{ph}")
                       for ph in ("compose", "guard", "refine", "execute",
                                  "audit", "prefill", "decode", "sync")}
        self._calls = {k: m.counter("decode_calls", kind=k)
                       for k in ("prefill", "decode")}
        #: MoE layers a call runs, counted under the local path a
        #: one-token call takes (``MoE.local_path``); none if dense
        self._n_moe = T.n_moe_layers(cfg)
        self._moe = (m.counter("moe_dispatch", path=MoE.local_path(cfg, 1))
                     if self._n_moe else None)
        if self._n_moe:
            # the experts a MoE layer holds here, of the router's width
            m.gauge("moe_experts_held").set(cfg.n_held)
            m.gauge("moe_router_experts").set(cfg.n_experts)
        self._tokens = m.counter("tokens_emitted")

    # -- workload characterisation -------------------------------------
    def _kv_bytes_per_token(self) -> float:
        cfg = self.cfg
        n_attn = sum(1 for i in range(cfg.n_layers)
                     if cfg.layer_kind(i) == "attn")
        if cfg.attn_type == "mla":
            per = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        else:
            per = 2 * cfg.n_kv_heads * cfg.head_dim
        return float(n_attn * per * 2)  # bf16

    def _work_items(self) -> list[tuple[TpuWorkItem, Request, str]]:
        items = []
        kvb = self._kv_bytes_per_token()
        for r in self.queue:
            if r.done:
                continue
            if r.cache is None:
                it = prefill_profile(f"prefill:{r.rid}",
                                     n_params=self.n_params,
                                     seq_len=int(len(r.prompt)),
                                     kv_bytes_per_token=kvb)
                items.append((it, r, "prefill"))
            else:
                it = decode_profile(f"decode:{r.rid}",
                                    n_params=self.n_params,
                                    kv_len=r.pos,
                                    kv_bytes_per_token=kvb)
                items.append((it, r, "decode"))
        return items

    def _work_items_dag(self):
        """Per-layer work items for the ``respect_deps`` path
        (see :func:`build_dag_triples`)."""
        reqs = [r for r in self.queue if not r.done]
        return build_dag_triples(
            self.cfg, reqs, n_params=self.n_params,
            kv_bytes_per_token=self._kv_bytes_per_token(),
            max_stages=self.policy.dag_max_stages)

    # -- execution -------------------------------------------------------
    def submit(self, reqs: list[Request]) -> None:
        self.queue.extend(reqs)
        for r in reqs:
            self.latency.arrive(r.rid)

    def replay_prefill(self, prompt) -> tuple[jnp.ndarray, object]:
        """Replay ``prompt`` through the decode step, one token at a
        time (correctness-first prefill).  Returns the last prompt
        token's logits, (1, vocab), and the filled cache."""
        toks = jnp.asarray(prompt, jnp.int32)[None, :]
        cache = T.init_cache(self.cfg, 1, self.max_len,
                             self.cfg.compute_dtype)
        for s in range(toks.shape[1]):
            logits, cache = self._call("prefill", toks[:, s], cache, s)
        return logits, cache

    def _call(self, kind: str, tok, cache, pos):
        """One ``decode_step`` call, counted under ``decode_calls{kind}``
        and, per MoE layer, under ``moe_dispatch{path}``."""
        out = _decode_step(self.params, self.cfg, tok, cache, pos)
        self._calls[kind].inc()
        if self._moe is not None:
            self._moe.inc(self._n_moe)
        return out

    def _emit(self, r: Request, logits) -> None:
        """Read back the chosen token (``phase_sync``) and record it
        with the time it was made."""
        with self._phase["sync"].time(rid=r.rid):
            tok = int(jnp.argmax(logits[0]))
        r.generated.append(tok)
        r.token_times.append(time.perf_counter())
        self._tokens.inc()

    def _exec_prefill(self, r: Request) -> None:
        self.latency.start(r.rid)
        with self._phase["prefill"].time(rid=r.rid,
                                         prompt_len=len(r.prompt)) as t:
            logits, r.cache = self.replay_prefill(r.prompt)
            r.pos = len(r.prompt)
            self._emit(r, logits)
        self.latency.charge(r.rid, "execute", t.elapsed)

    def _exec_decode(self, r: Request) -> None:
        with self._phase["decode"].time(rid=r.rid, pos=r.pos) as t:
            tok = jnp.asarray([r.generated[-1]], jnp.int32)
            logits, r.cache = self._call("decode", tok, r.cache, r.pos)
            r.pos += 1
            self._emit(r, logits)
        self.latency.charge(r.rid, "execute", t.elapsed)
        if (len(r.generated) >= r.max_new_tokens or
                r.pos >= self.max_len - 1):
            r.done = True

    def step(self) -> int:
        """One scheduling iteration: compose rounds from the current
        queue and execute them.  Returns the number of rounds run.

        On the ``respect_deps`` path a round may contain interior
        chain stages (kind ``"frag"``): they contribute to the round's
        modelled time but trigger no execution — the request's exact
        forward pass runs once, at its chain's tail item.  With
        ``composition="incremental"`` the traced step composes through
        the live frontier instead of the batch pipeline.

        Observability: the whole composition pipeline is timed under
        the ``phase_compose`` histogram and the execution loop under
        ``phase_execute`` (the composer's own ``phase_guard`` /
        ``phase_refine`` are sub-intervals of compose); inside execute,
        each request's replayed prompt is ``phase_prefill``, each decode
        call ``phase_decode``, and the argmax read-back in either
        ``phase_sync`` (see :mod:`repro.obs.profile`; every timer is a
        profiler span too).  Sampled steps run the online quality audit
        under ``phase_audit`` (outside compose, so audit cost never
        skews the compose-time series); with :attr:`trace` set, each
        executed round is recorded on the modelled-round timeline.  A
        request's queue span closes when its prefill starts, its own
        prefill and decode seconds are its execute time, and the
        step's compose time is split across the requests it served
        (:class:`repro.obs.LatencyTracker`)."""
        self._steps.inc()
        phase = self._phase
        phase0 = {ph: phase[ph].total for ph in ("compose", "guard",
                                                 "refine")}
        traced = None
        with phase["compose"].time():
            if self.policy.respect_deps:
                triples, traced = self._work_items_dag()
                if not triples:
                    return 0
                if self.live is not None:
                    rounds = self.live.compose_dag(triples, traced)
                else:
                    rounds = self.composer.compose_dag(triples, traced)
                time_of = self.composer.dag_round_time
            else:
                items = self._work_items()
                if not items:
                    return 0
                rounds = self.composer.compose(items)
                time_of = lambda rd: round_time(  # noqa: E731
                    [t[0] for t in rd], self.device, self.weights_bytes)
        # Online quality audit (PR 9): read-only over the composed
        # rounds, on deterministically sampled steps only.
        aud = self.composer.auditor
        if aud.sample_step():
            with phase["audit"].time():
                if traced is not None:
                    aud.audit_dag(rounds, traced, arch=self.cfg.name,
                                  kind=self.policy.kind)
                else:
                    aud.audit_flat(rounds,
                                   weights_bytes=self.weights_bytes,
                                   arch=self.cfg.name,
                                   kind=self.policy.kind)
        n = 0
        with phase["execute"].time():
            for rd in rounds:
                rt = time_of(rd)
                self._round_times.append(rt)
                if self.trace is not None:
                    t0 = self._trace_t
                    for it, r, kind in rd:
                        self.trace.span(0, it.name, t0, t0 + rt,
                                        cat=kind)
                    self.trace.instant(
                        f"round {len(self._round_times) - 1}",
                        t0 + rt, unit=0, cat="round")
                    self.trace.add_busy(0, rt)
                self._trace_t += rt
                for it, r, kind in rd:
                    if kind == "prefill":
                        self._exec_prefill(r)
                    elif kind == "decode":
                        self._exec_decode(r)
                n += 1
        # Latency accounting: split this step's shared phase wall times
        # across the requests it served ("compose" net of its
        # guard/refine sub-intervals), then close the spans of requests
        # that just finished (a span closes once: complete() drops it).
        delta = {ph: phase[ph].total - t0 for ph, t0 in phase0.items()}
        delta["compose"] = max(
            0.0, delta["compose"] - delta["guard"] - delta["refine"])
        served = {r.rid: r for rd in rounds for _, r, _ in rd}
        self.latency.attribute(served.keys(), delta)
        for rid, r in served.items():
            if r.done:
                self.latency.complete(rid, tokens=len(r.generated))
        return n

    def run(self, max_iters: int = 10_000,
            arrivals: list[tuple[int, list[Request]]] | None = None) -> dict:
        """Run to completion; returns stats incl. modelled round times.

        ``arrivals``: optional [(iteration, requests)] injections — a
        continuous-arrival workload where prefill and decode work
        genuinely coexist in the queue.

        The returned stats carry (PR 9) a ``"latency"`` block —
        per-request arrival→completion p50/p95/p99, queue quantiles,
        mean per-phase attribution and goodput over the run's wall
        time (:meth:`repro.obs.LatencyTracker.stats`)."""
        import time as _time

        t_wall0 = _time.perf_counter()
        arrivals = list(arrivals or [])
        n_rounds = 0
        iters = 0
        while iters < max_iters:
            for when, reqs in list(arrivals):
                if when <= iters:
                    self.submit(reqs)
                    arrivals.remove((when, reqs))
            ran = self.step()
            if ran == 0 and not arrivals:
                break
            n_rounds += ran
            iters += 1
        total_tokens = sum(len(r.generated) for r in self.queue)
        return {
            "rounds": n_rounds,
            "total_new_tokens": total_tokens,
            "modelled_time_s": float(sum(self._round_times)),
            "modelled_tokens_per_s": total_tokens /
            max(sum(self._round_times), 1e-12),
            "schedule_cache": self.schedule_cache.stats(),
            "metrics": self.metrics.snapshot(),
            "phases": phase_breakdown(self.metrics),
            "latency": self.latency.stats(
                _time.perf_counter() - t_wall0),
            "outputs": {r.rid: list(r.generated) for r in self.queue},
        }
