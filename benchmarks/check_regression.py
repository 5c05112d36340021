"""CI guard: fail on schedule-construction wall-time regressions.

Re-runs the ``benchmarks/scaling.py`` fast-path construction cells on
this machine and diffs them against the committed
``BENCH_scheduler_scaling.json``: any (scenario, n) whose fresh
``path="fast"`` wall time exceeds the committed one by more than
``--threshold`` (default 1.25x, plus ``--abs-slack`` seconds so
second-scale cells don't flap on scheduler/runner jitter — on a
shared single-core box the sub-second refine cells swing several
hundred ms run-to-run, so the absolute slack, not the ratio, is what
keeps them stable) fails the check with exit code 1.  The event-refine delta cells are compared the same
way (their wall time is the event-model refinement hot path).  Both
sides use best-of-``--repeats`` wall times (the committed JSON records
its own ``repeats``), the standard protocol for wall-clock guards.

An absolute floor rides along (a load-insensitive ratio of two fresh
runs on the same box, so it needs no committed baseline): the
incremental-vs-batch compose-time speedup under serving churn
(``--churn-floor``, re-running ``benchmarks/serving.py``'s
``churn_compose_bench`` at its largest ``n_live`` cell).  The ``repro.serve`` re-export surface is also
import-checked, so the PR 7 package split can't silently drop the
historical flat names.

This is a same-machine tool: committed numbers are only comparable to
runs on comparable hardware, so the intended use is "run the benchmark
before and after a change on one box" (or a pinned CI runner), not
cross-machine comparison.

Run:  PYTHONPATH=src python benchmarks/check_regression.py
      PYTHONPATH=src python benchmarks/check_regression.py --quick
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import scaling  # noqa: E402

#: cells whose wall time is a guarded hot path (``dag_fast`` is the
#: ready-set constrained greedy, repro.graph.greedy_order_dag;
#: ``slice_fast`` the lazy slice-aware greedy,
#: repro.slice.greedy_order_slices; ``dag_refine_gated`` the gated
#: delta-refinement path, repro.graph.delta.GatedDeltaEvaluator via
#: refine_order_dag(model="gated"))
_GUARDED_PATHS = ("fast", "event_delta", "dag_fast", "slice_fast",
                  "dag_refine_gated")

#: floor on the fresh incremental-vs-batch compose-time speedup at the
#: churn benchmark's largest n_live cell (benchmarks/serving.py,
#: ``churn_compose_bench``; the committed BENCH_serving.json records
#: >= 2x at 64 live requests; the guard is deliberately looser, so it
#: catches a live path that degenerated into rebuild-every-step
#: without flapping on runner noise)
_CHURN_FLOOR = 1.6

#: ceiling on the tracing-enabled / tracing-disabled wall-time ratio
#: of a compose + gated-simulate pass (PR 8: every instrumentation
#: site is a ``trace is not None`` guard plus a list append, so a
#: live :class:`repro.obs.ScheduleTrace` must stay within 10% of the
#: null recorder — a hot-path emission that got expensive shows up
#: here before it shows up in serving step times)
_TRACE_OVERHEAD = 1.10

#: ceiling on the audit-on / audit-off wall-time ratio of the serving
#: compose loop at ``audit_frac=0.05`` (PR 9: the online Fig.-1 audit
#: re-scores one served step in twenty against 50 delta-evaluated
#: random orders, so the sampled audits must amortize to within 15%
#: of the audit-off loop — checkpoint reuse in the
#: GatedDeltaEvaluator is what keeps this affordable, and a change
#: that degrades it to K full simulations per audit shows up here)
_AUDIT_OVERHEAD = 1.15

#: ceiling on the frontend / bare-synchronous wall-time ratio over the
#: same request set with every arrival at t=0 (saturation: the queue
#: is never empty, so admission-control costing, routing and the
#: virtual-clock event loop all run on every dispatch).  PR 10's front
#: end is bookkeeping around the same ``engine.step()`` calls, so it
#: must stay within 15% of the bare loop — an admission scan that went
#: quadratic-expensive or a per-dispatch recompose shows up here.
_FRONTEND_OVERHEAD = 1.15

#: the PR 7 package split re-exports the historical flat import
#: surface; a rename that silently drops one of these breaks every
#: external consumer, so the guard imports them by name
_SERVE_SURFACE = ("Request", "ScheduleCache", "SchedulerPolicy",
                  "ServingEngine", "Signature")

#: PR 10 async-serving surface, same discipline per module
_FRONTEND_SURFACE = {
    "repro.serve": ("ServingFrontend", "AdmissionPolicy",
                    "VirtualClock", "LoadGenerator", "make_workload"),
    "repro.serve.frontend": ("ServingFrontend", "AdmissionPolicy",
                             "VirtualClock"),
    "repro.serve.loadgen": ("LoadGenerator", "make_workload",
                            "poisson_arrivals", "bursty_arrivals",
                            "diurnal_arrivals"),
}


def trace_overhead_ratio(*, repeats: int = 7, inner: int | None = None,
                         min_sample_s: float = 0.05) -> dict:
    """Wall-time ratio of a traced vs untraced compose + simulate
    pass: the ready-set greedy over a traced qwen arch on the x4
    serving device, then :class:`repro.graph.streams.DagEventSimulator`
    with a live :class:`repro.obs.ScheduleTrace` vs ``trace=None``.

    Interleaved best-of-``repeats`` (each repeat times both sides
    back-to-back) so slow drift on a shared runner hits both sides
    equally.  ``inner`` (passes per timed sample) defaults to
    whatever makes one untraced sample take at least
    ``min_sample_s`` — a single compose+simulate pass is sub-ms, and
    a ratio of two sub-ms samples flaps on any scheduler hiccup, so
    the sample is stretched until the 10% headroom is milliseconds
    wide and best-of-k can actually filter the noise."""
    import time

    from repro.configs import get_config
    from repro.core.tpu import make_serving_device
    from repro.graph.constrained import greedy_order_dag
    from repro.graph.kernel_graph import trace_arch
    from repro.graph.streams import DagEventSimulator
    from repro.obs import ScheduleTrace

    cfg = get_config("qwen1.5-0.5b", "full")
    traced = trace_arch(cfg, [("prefill", 64)] * 3
                        + [("decode", 128)] * 3, max_stages=48)
    g = traced.graph
    device = make_serving_device(n_units=4)
    eids = g.edges_by_id()

    def once(with_trace: bool, n: int = 1) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            sched = greedy_order_dag(g.kernels, device, edges=g.edges)
            tr = ScheduleTrace() if with_trace else None
            DagEventSimulator(device, eids).simulate(sched.order,
                                                     trace=tr)
        return time.perf_counter() - t0

    warm = once(False)                # warm caches on neither side
    if inner is None:
        # calibrate: stretch the sample until one untraced timing is
        # at least min_sample_s, so the gate compares multi-ms walls
        inner = max(1, int(math.ceil(min_sample_s / max(warm, 1e-6))))
    t_off = t_on = float("inf")
    for _ in range(max(repeats, 1)):
        t_off = min(t_off, once(False, inner))
        t_on = min(t_on, once(True, inner))
    return {"wall_off_s": t_off, "wall_on_s": t_on, "inner": inner,
            "ratio": t_on / max(t_off, 1e-12)}


def audit_overhead_ratio(*, repeats: int = 7, inner: int | None = None,
                         min_sample_s: float = 0.05,
                         frac: float = 0.05, k: int = 50) -> dict:
    """Wall-time ratio of the serving compose loop with the online
    quality audit sampling at ``frac`` vs auditing disabled.

    Model-free replica of the engine's step hook: each pass composes a
    traced qwen step cold (``kind="refined"``, gated refinement and
    guard) and, on the auditor's deterministic ``frac`` sample, scores
    it against ``k`` random topological orders through
    :class:`repro.obs.QualityAuditor` — exactly what
    ``audit_frac=0.05`` costs a serving engine.  Interleaved
    best-of-``repeats`` like :func:`trace_overhead_ratio`, with one
    twist: the timed sample is stretched to a multiple of the sampling
    period ``1/frac`` so every sample pays the same whole number of
    audits (a fractional period would make the ratio depend on where
    the sample window cuts the deterministic audit pattern)."""
    import time

    import numpy as np

    from repro.configs import get_config
    from repro.core.tpu import make_serving_device
    from repro.graph.kernel_graph import (arch_kv_bytes_per_token,
                                          estimate_n_params)
    from repro.serve import (Composer, Request, ScheduleCache,
                             SchedulerPolicy, build_dag_triples)

    cfg = get_config("qwen1.5-0.5b", "full")
    n_params = estimate_n_params(cfg)
    kvb = arch_kv_bytes_per_token(cfg)
    decoded = object()   # build_dag_triples only checks `cache is None`
    reqs = []
    for rid, (phase, n) in enumerate([("prefill", 64)] * 2
                                     + [("decode", 128 * (i + 1))
                                        for i in range(3)]):
        r = Request(rid, np.zeros(n, np.int32))
        if phase == "decode":
            r.cache, r.pos = decoded, n
        reqs.append(r)
    # small step graph: one timed sample is 1/frac composes, so the
    # per-step cost sets the gate's total wall time
    triples, traced = build_dag_triples(cfg, reqs, n_params=n_params,
                                        kv_bytes_per_token=kvb,
                                        max_stages=8)
    device = make_serving_device(n_units=4)

    def once(f: float, n: int = 1) -> float:
        # fresh composer per sample: the auditor's step counter
        # restarts, so every audit-on sample fires the identical
        # deterministic audit pattern
        pol = SchedulerPolicy(kind="refined", respect_deps=True,
                              refine_model="gated", dag_guard="gated",
                              cache=False, audit_frac=f, audit_k=k)
        comp = Composer(pol, device, 2.0 * n_params, ScheduleCache())
        aud = comp.auditor
        t0 = time.perf_counter()
        for _ in range(n):
            rounds = comp.compose_dag(triples, traced)
            if aud.sample_step():
                aud.audit_dag(rounds, traced, arch=cfg.name,
                              kind="refined")
        return time.perf_counter() - t0

    warm = once(0.0)                  # warm caches on neither side
    period = max(1, round(1.0 / frac))
    if inner is None:
        inner = max(1, int(math.ceil(min_sample_s / max(warm, 1e-6))))
    inner = period * int(math.ceil(inner / period))
    t_off = t_on = float("inf")
    for _ in range(max(repeats, 1)):
        t_off = min(t_off, once(0.0, inner))
        t_on = min(t_on, once(frac, inner))
    return {"wall_off_s": t_off, "wall_on_s": t_on, "inner": inner,
            "audit_frac": frac, "audit_k": k,
            "audits_per_sample": inner // period,
            "ratio": t_on / max(t_off, 1e-12)}


def frontend_overhead_ratio(*, repeats: int = 7,
                            inner: int | None = None,
                            min_sample_s: float = 0.05,
                            n_requests: int = 6) -> dict:
    """Wall-time ratio of the async front end vs the bare synchronous
    ``ServingEngine`` loop over the *same request set* at saturation
    (every arrival at virtual t=0, so the arrival queue is never empty
    and admission costing + routing + the event loop run on every
    dispatch).

    Engines are built and jit-warmed *outside* the timed region (a
    fresh engine recompiles its decode step; both sides would pay it,
    but it would drown the bookkeeping delta this gate exists to
    bound).  Interleaved best-of-``repeats`` with the sample stretched
    to at least ``min_sample_s`` like the other overhead gates."""
    import time

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import (AdmissionPolicy, Request, SchedulerPolicy,
                             ServingEngine, ServingFrontend)

    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)

    def mk_engine() -> ServingEngine:
        eng = ServingEngine(cfg, params, max_len=32,
                            policy=SchedulerPolicy())
        # jit-warm with a throwaway request (compile out of the timing)
        eng.submit([Request(-1, np.zeros(2, np.int32),
                            max_new_tokens=1)])
        eng.run()
        return eng

    def mk_reqs() -> list[Request]:
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(0, 128, size=4).astype(np.int32),
                        max_new_tokens=4) for i in range(n_requests)]

    def once(front: bool, n: int = 1) -> float:
        engines = [mk_engine() for _ in range(n)]
        batches = [mk_reqs() for _ in range(n)]
        t0 = time.perf_counter()
        for eng, batch in zip(engines, batches):
            if front:
                fe = ServingFrontend(
                    [eng], AdmissionPolicy(round_cost_budget_s=1.0))
                fe.run([(0.0, r) for r in batch])
            else:
                eng.submit(batch)
                eng.run()
        return time.perf_counter() - t0

    warm = once(False)                # warm caches on neither side
    if inner is None:
        inner = max(1, int(math.ceil(min_sample_s / max(warm, 1e-6))))
    t_off = t_on = float("inf")
    for _ in range(max(repeats, 1)):
        t_off = min(t_off, once(False, inner))
        t_on = min(t_on, once(True, inner))
    return {"wall_off_s": t_off, "wall_on_s": t_on, "inner": inner,
            "n_requests": n_requests,
            "ratio": t_on / max(t_off, 1e-12)}


def _surface_regressions() -> list[str]:
    out = []
    surfaces = {"repro.serve": _SERVE_SURFACE,
                "repro.serve.engine": _SERVE_SURFACE}
    for mod, names in list(surfaces.items()) + \
            list(_FRONTEND_SURFACE.items()):
        try:
            m = __import__(mod, fromlist=list(names))
        except ImportError as e:
            out.append(f"import surface: {mod} failed to import ({e})")
            continue
        for name in names:
            if not hasattr(m, name):
                out.append(f"import surface: {mod}.{name} is gone")
    return out


def compare(committed: dict, fresh: dict, threshold: float,
            abs_slack: float = 0.75) -> list[str]:
    """Regression messages for every guarded cell above threshold."""
    old = {(r["scenario"], r["n"], r["path"]): r["wall_s"]
           for r in committed.get("results", [])
           if r["path"] in _GUARDED_PATHS}
    regressions = []
    for r in fresh.get("results", []):
        key = (r["scenario"], r["n"], r["path"])
        if r["path"] not in _GUARDED_PATHS or key not in old:
            continue
        base = old[key]
        if base > 0 and r["wall_s"] > base * threshold + abs_slack:
            regressions.append(
                f"{key[0]}@n={key[1]}[{key[2]}]: "
                f"{r['wall_s']:.3f}s vs committed {base:.3f}s "
                f"({r['wall_s'] / base:.2f}x > {threshold:.2f}x)")
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_scheduler_scaling.json"),
        help="committed benchmark JSON to diff against")
    ap.add_argument("--threshold", type=float, default=1.25)
    ap.add_argument("--abs-slack", type=float, default=0.75,
                    help="absolute seconds of slack on top of the "
                         "ratio threshold (runner-jitter floor: "
                         "sub-second refine cells swing hundreds of "
                         "ms on a shared single-core runner)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="best-of-k for the fresh run (default: the "
                         "committed JSON's own repeats)")
    ap.add_argument("--churn-floor", type=float, default=_CHURN_FLOOR,
                    help="minimum incremental/batch compose-time "
                         "speedup at the churn benchmark's largest "
                         "n_live cell (0 disables; re-runs "
                         "benchmarks/serving.py churn_compose_bench "
                         "fresh)")
    ap.add_argument("--trace-overhead", type=float,
                    default=_TRACE_OVERHEAD,
                    help="ceiling on the traced/untraced wall-time "
                         "ratio of a compose + gated-simulate pass "
                         "(0 disables; interleaved best-of-k on this "
                         "box, no committed baseline needed)")
    ap.add_argument("--audit-overhead", type=float,
                    default=_AUDIT_OVERHEAD,
                    help="ceiling on the audit-on/audit-off wall-time "
                         "ratio of the serving compose loop at "
                         "audit_frac=0.05 (0 disables; interleaved "
                         "best-of-k on this box, no committed "
                         "baseline needed)")
    ap.add_argument("--frontend-overhead", type=float,
                    default=_FRONTEND_OVERHEAD,
                    help="ceiling on the async-frontend/bare-engine "
                         "wall-time ratio over the same request set "
                         "at saturation arrival rate (0 disables; "
                         "interleaved best-of-k on this box, no "
                         "committed baseline needed)")
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow oracle/full baselines entirely "
                         "(fresh run measures only the guarded cells)")
    ap.add_argument("--out", default=None,
                    help="optionally write the fresh run's JSON here")
    args = ap.parse_args(argv)

    with open(args.bench) as f:
        committed = json.load(f)
    # The guarded cells are the fast/delta paths; the reference oracle
    # and full-re-sim baselines only provide speedup context, so the
    # fresh run can skip them (--quick) without losing coverage.
    max_ref = 0 if args.quick else committed.get("max_ref_n", 512)
    max_event_full = (0 if args.quick
                      else committed.get("max_event_full_n", 256))
    max_gated_full = (0 if args.quick
                      else committed.get("max_gated_full_n", 128))
    repeats = (args.repeats if args.repeats is not None
               else committed.get("repeats", 2))
    fresh = scaling.run(max_ref_n=max_ref,
                        max_event_full_n=max_event_full,
                        max_gated_full_n=max_gated_full,
                        repeats=repeats)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(fresh, f, indent=2)
    regressions = compare(committed, fresh, args.threshold,
                          args.abs_slack)
    regressions += _surface_regressions()
    if args.churn_floor > 0:
        import serving
        rows = serving.churn_compose_bench(print_fn=lambda *_: None)
        top = max(rows, key=lambda r: r["n_live"])
        if top["compose_speedup"] < args.churn_floor:
            regressions.append(
                f"incremental compose speedup under churn at "
                f"n_live={top['n_live']}: "
                f"{top['compose_speedup']:.2f}x < floor "
                f"{args.churn_floor:.2f}x")
    if args.trace_overhead > 0:
        tr = trace_overhead_ratio()
        if tr["ratio"] > args.trace_overhead:
            regressions.append(
                f"schedule-trace overhead: traced compose+simulate "
                f"{tr['ratio']:.3f}x untraced "
                f"({tr['wall_on_s'] * 1e3:.1f} ms vs "
                f"{tr['wall_off_s'] * 1e3:.1f} ms) > ceiling "
                f"{args.trace_overhead:.2f}x")
    if args.audit_overhead > 0:
        au = audit_overhead_ratio()
        if au["ratio"] > args.audit_overhead:
            regressions.append(
                f"online-audit overhead: audit_frac={au['audit_frac']} "
                f"compose loop {au['ratio']:.3f}x audit-off "
                f"({au['wall_on_s'] * 1e3:.1f} ms vs "
                f"{au['wall_off_s'] * 1e3:.1f} ms, "
                f"{au['audits_per_sample']} audits/sample) > ceiling "
                f"{args.audit_overhead:.2f}x")
    if args.frontend_overhead > 0:
        fr = frontend_overhead_ratio()
        if fr["ratio"] > args.frontend_overhead:
            regressions.append(
                f"async-frontend overhead: saturated dispatch loop "
                f"{fr['ratio']:.3f}x the bare synchronous engine "
                f"({fr['wall_on_s'] * 1e3:.1f} ms vs "
                f"{fr['wall_off_s'] * 1e3:.1f} ms over "
                f"{fr['n_requests']} requests) > ceiling "
                f"{args.frontend_overhead:.2f}x")
    if regressions:
        print("\nREGRESSION: construction wall time exceeded "
              f"{args.threshold:.2f}x the committed baseline:")
        for msg in regressions:
            print(f"  {msg}")
        return 1
    n_cells = sum(1 for r in fresh["results"]
                  if r["path"] in _GUARDED_PATHS)
    print(f"\nok: {n_cells} guarded cells within "
          f"{args.threshold:.2f}x of committed baseline")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
