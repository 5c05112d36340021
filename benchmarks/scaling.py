"""Schedule-construction scaling: reference vs vectorized paths.

For n in {8, 32, 128, 512, 1024} kernels, on two workload mixes
(GTX580 kernel soup; TPU serving prefill+decode items), measures

* wall time of schedule construction — greedy + default-budget refine
  (200 evaluations, the serving default) — for the pure-Python
  reference path (the test-only oracle) vs the vectorized/incremental
  fast path, and
* the modelled execution time of the produced order under both the
  round model (the refine objective) and the event simulator,

plus a **DAG-constrained construction** section (the ready-set greedy
``repro.graph.greedy_order_dag`` over chain-structured random DAGs,
path ``dag_fast`` — guarded by ``check_regression.py`` alongside the
flat fast path), and a second section for **event-model refinement** at n in
{64, 128, 256, 512, 1024}: full re-simulation per candidate (the
reference ``EventSimulator``, the pre-checkpointing status quo) vs
the checkpointing delta path (``refine_order(model="event")``, suffix
re-simulation via ``DeltaEvaluator``), reporting effective-move
throughput (candidate moves evaluated per second) for both.  The
acceptance bar is >= 5x delta throughput at n = 256.

A **gated-DAG refinement** section (ISSUE 5) measures
``refine_order_dag(model="gated")`` over the same chain-structured
DAGs: the checkpointing gated delta path
(``repro.graph.delta.GatedDeltaEvaluator``, path ``dag_refine_gated``
— guarded by ``check_regression.py``) vs full gated re-simulation per
candidate (``DagEventSimulator`` as ``time_fn``, path
``dag_refine_gated_full``, skipped above ``--max-gated-full-n``).

Emits ``BENCH_scheduler_scaling.json`` for the perf trajectory
(consumed by ``benchmarks/check_regression.py``).  The reference
construction path is O(R * n^2) Python-level ScoreGen reruns and is
skipped above ``--max-ref-n`` (default 512, ~35 s there); pass
``--full`` to run it everywhere.  The full-re-sim event-refine path
is skipped above ``--max-event-full-n`` (default 256).

Run:  PYTHONPATH=src python benchmarks/scaling.py
"""

from __future__ import annotations

import argparse
import json
import random
import time

from repro.core import (GTX580, EventSimulator, RoundSimulator,
                        greedy_order, greedy_order_fast, simulate)
from repro.core.refine import refine_order
from repro.core.resources import (KernelProfile, bs_kernel, ep_kernel,
                                  es_kernel, sw_kernel)
from repro.core.tpu import decode_profile, make_serving_device, prefill_profile
from repro.graph import (DagEventSimulator, KernelGraph, greedy_order_dag,
                         refine_order_dag)
from repro.slice import SlicePolicy, greedy_order_slices

REFINE_BUDGET = 200
NS = (8, 32, 128, 512, 1024)
#: event-model refine: budget in full-simulation equivalents, and the
#: ns it is measured at (the serving-relevant 64..1024 band).  Kept
#: deliberately small: event re-simulation is the expensive objective,
#: and a serving deployment would spend far less on it than the
#: round-model default of 200.
EVENT_BUDGET = 40
EVENT_NS = (64, 128, 256, 512, 1024)
#: gated-DAG refine (ISSUE 5): same budget discipline, smaller band —
#: each gated full sim walks the whole dependency frontier, so the
#: full-re-sim baseline is capped separately (--max-gated-full-n).
GATED_NS = (64, 128, 256, 512)
_FAMS = [ep_kernel, bs_kernel, es_kernel, sw_kernel]


def gpu_mix(rng: random.Random, n: int) -> list[KernelProfile]:
    return [rng.choice(_FAMS)(f"k{i}",
                              grid=rng.choice([8, 16, 32, 48, 64, 96]),
                              shm=rng.choice([0, 4096, 8192, 16384, 24576]),
                              inst=rng.uniform(1e6, 5e8))
            for i in range(n)]


def tpu_mix(rng: random.Random, n: int) -> list[KernelProfile]:
    out = []
    for i in range(n):
        if rng.random() < 0.3:
            it = prefill_profile(f"p{i}", n_params=7e9,
                                 seq_len=rng.choice([128, 256, 512, 1024]),
                                 kv_bytes_per_token=131072)
        else:
            it = decode_profile(f"d{i}", n_params=7e9,
                                kv_len=rng.randint(64, 8192),
                                kv_bytes_per_token=131072)
        out.append(it.profile())
    return out


SCENARIOS = (
    ("gpu_mix", GTX580, gpu_mix),
    ("tpu_serving", make_serving_device(), tpu_mix),
)


def _best_of(repeats: int, fn):
    """Run ``fn`` ``repeats`` times; keep the record with the smallest
    wall time.  Construction is deterministic, so min-of-k only strips
    scheduler/host noise from the timing — the standard protocol for
    wall-clock guards (``check_regression.py`` compares min against
    min)."""
    best = None
    for _ in range(max(repeats, 1)):
        rec = fn()
        if best is None or rec["wall_s"] < best["wall_s"]:
            best = rec
    return best


def construct(ks, device, path: str) -> dict:
    """Greedy + default-budget refine; returns wall time + quality."""
    t0 = time.perf_counter()
    if path == "reference":
        sched = greedy_order(ks, device)
        sim = RoundSimulator(device)
        order, t_round, evals = refine_order(
            sched.order, device, time_fn=sim.simulate,
            budget=REFINE_BUDGET)
    else:
        sched = greedy_order_fast(ks, device)
        order, t_round, evals = refine_order(
            sched.order, device, model="round", budget=REFINE_BUDGET,
            neighborhood="auto")
    wall = time.perf_counter() - t0
    return {
        "path": path,
        "wall_s": wall,
        "rounds": len(sched.rounds),
        "refine_evals": evals,
        "modelled_round_time_s": t_round,
        "modelled_event_time_s": simulate(order, device),
    }


def chain_edges(rng: random.Random, n: int,
                width: int) -> set[tuple[int, int]]:
    """``width`` parallel chains over ``n`` kernels (the traced-arch
    edge shape: intra-request chains, cross-request independence)."""
    edges: set[tuple[int, int]] = set()
    chains: list[list[int]] = [[] for _ in range(max(width, 1))]
    for i in range(n):
        c = chains[rng.randrange(len(chains))]
        if c:
            edges.add((c[-1], i))
        c.append(i)
    return edges


def dag_construct(ks, edges, device) -> dict:
    """Ready-set greedy construction over a kernel DAG; wall time is
    the guarded quantity (``check_regression.py``, path="dag_fast")."""
    t0 = time.perf_counter()
    sched = greedy_order_dag(ks, device, edges=edges)
    wall = time.perf_counter() - t0
    assert KernelGraph(ks, edges).is_topological(sched.order)
    return {"path": "dag_fast", "wall_s": wall,
            "rounds": len(sched.rounds), "n_edges": len(edges)}


def slice_mix(rng: random.Random, n: int) -> list[KernelProfile]:
    """TPU serving mix with ~12% oversized prefill stages (tokens
    above the 4096-slot round budget) — the workload shape the lazy
    slice greedy exists for."""
    out = []
    for i in range(n):
        u = rng.random()
        if u < 0.12:
            it = prefill_profile(f"P{i}", n_params=7e9,
                                 seq_len=rng.choice([6144, 8192, 12288]),
                                 kv_bytes_per_token=131072)
        elif u < 0.3:
            it = prefill_profile(f"p{i}", n_params=7e9,
                                 seq_len=rng.choice([128, 256, 512, 1024]),
                                 kv_bytes_per_token=131072)
        else:
            it = decode_profile(f"d{i}", n_params=7e9,
                                kv_len=rng.randint(64, 8192),
                                kv_bytes_per_token=131072)
        out.append(it.profile())
    return out


def slice_construct(ks, edges, device) -> dict:
    """Lazy slice-aware greedy construction
    (``repro.slice.greedy_order_slices``); wall time is the guarded
    quantity (``check_regression.py``, path="slice_fast")."""
    t0 = time.perf_counter()
    res = greedy_order_slices(ks, device, edges=edges,
                              policy=SlicePolicy())
    wall = time.perf_counter() - t0
    assert res.graph().is_topological(res.order)
    return {"path": "slice_fast", "wall_s": wall,
            "rounds": len(res.rounds), "n_edges": len(res.edges),
            "n_sliced": len(res.sliced),
            "n_expanded": len(res.kernels)}


def gated_refine(ks, edges, device, path: str) -> dict:
    """Gated-model local search on the constrained greedy order:
    checkpointing delta path (``dag_refine_gated`` — the guarded
    cell) vs full gated re-simulation per candidate
    (``dag_refine_gated_full``)."""
    g = KernelGraph(ks, edges)
    eids = g.edges_by_id()
    order = greedy_order_dag(ks, device, edges=edges).order
    t0 = time.perf_counter()
    if path == "dag_refine_gated_full":
        sim = DagEventSimulator(device, eids)
        _, t_g, evals = refine_order_dag(
            order, device, edge_ids=eids, time_fn=sim.simulate,
            budget=EVENT_BUDGET, neighborhood="adjacent")
    else:
        _, t_g, evals = refine_order_dag(
            order, device, edge_ids=eids, model="gated",
            budget=EVENT_BUDGET, neighborhood="adjacent")
    wall = time.perf_counter() - t0
    return {"path": path, "wall_s": wall, "refine_evals": evals,
            "moves_per_s": evals / max(wall, 1e-9),
            "modelled_gated_time_s": t_g, "n_edges": len(edges)}


def event_refine(ks, device, path: str) -> dict:
    """Event-model local search on the greedy order; returns wall time,
    evaluated moves and effective-move throughput."""
    order = greedy_order_fast(ks, device).order
    t0 = time.perf_counter()
    if path == "event_full":
        sim = EventSimulator(device)
        _, t_ev, evals = refine_order(
            order, device, time_fn=sim.simulate,
            budget=EVENT_BUDGET, neighborhood="adjacent")
    else:
        _, t_ev, evals = refine_order(
            order, device, model="event", budget=EVENT_BUDGET,
            neighborhood="adjacent")
    wall = time.perf_counter() - t0
    return {"path": path, "wall_s": wall, "refine_evals": evals,
            "moves_per_s": evals / max(wall, 1e-9),
            "modelled_event_time_s": t_ev}


def run(max_ref_n: int = 512, seed: int = 0, max_event_full_n: int = 256,
        max_gated_full_n: int = 128, repeats: int = 2,
        print_fn=print) -> dict:
    results = []
    print_fn("# Scheduler scaling: reference vs vectorized "
             f"(refine budget {REFINE_BUDGET}, best of {repeats})")
    print_fn("scenario,n,path,wall_s,round_time_s,event_time_s,speedup")
    for name, device, maker in SCENARIOS:
        for n in NS:
            rng = random.Random(seed)
            ks = maker(rng, n)
            fast = _best_of(repeats,
                            lambda: construct(ks, device, "fast"))
            ref = None
            if n <= max_ref_n:
                # Same best-of-k protocol as the fast cell: asymmetric
                # sampling would systematically inflate the speedups.
                ref = _best_of(repeats,
                               lambda: construct(ks, device, "reference"))
            for rec in filter(None, (ref, fast)):
                speedup = (ref["wall_s"] / fast["wall_s"]
                           if ref is not None and rec is fast else "")
                print_fn(f"{name},{n},{rec['path']},"
                         f"{rec['wall_s']:.4f},"
                         f"{rec['modelled_round_time_s']:.5f},"
                         f"{rec['modelled_event_time_s']:.5f},"
                         f"{speedup if speedup == '' else f'{speedup:.1f}'}")
                results.append({"scenario": name, "n": n, **rec})
    print_fn("# DAG-constrained construction (ready-set greedy, "
             f"chain-structured edges, best of {repeats})")
    print_fn("scenario,n,path,wall_s,rounds,n_edges")
    for n in NS:
        rng = random.Random(seed)
        ks = gpu_mix(rng, n)
        edges = chain_edges(rng, n, width=max(4, n // 8))
        rec = _best_of(repeats,
                       lambda: dag_construct(ks, edges, GTX580))
        print_fn(f"gpu_dag,{n},{rec['path']},{rec['wall_s']:.4f},"
                 f"{rec['rounds']},{rec['n_edges']}")
        results.append({"scenario": "gpu_dag", "n": n, **rec})
    print_fn("# Sliced-DAG construction (lazy slice greedy, oversized "
             f"TPU serving mix, best of {repeats})")
    print_fn("scenario,n,path,wall_s,rounds,n_sliced,n_expanded")
    tpu_dev = make_serving_device()
    for n in NS:
        rng = random.Random(seed)
        ks = slice_mix(rng, n)
        edges = chain_edges(rng, n, width=max(4, n // 8))
        rec = _best_of(repeats,
                       lambda: slice_construct(ks, edges, tpu_dev))
        print_fn(f"tpu_slice,{n},{rec['path']},{rec['wall_s']:.4f},"
                 f"{rec['rounds']},{rec['n_sliced']},{rec['n_expanded']}")
        results.append({"scenario": "tpu_slice", "n": n, **rec})
    print_fn("# Event-model refine: full re-sim vs checkpoint delta "
             f"(budget {EVENT_BUDGET} full-sim equivalents)")
    print_fn("scenario,n,path,wall_s,evals,moves_per_s,throughput_ratio")
    for n in EVENT_NS:
        rng = random.Random(seed)
        ks = gpu_mix(rng, n)
        delta = _best_of(repeats,
                         lambda: event_refine(ks, GTX580, "event_delta"))
        full = None
        if n <= max_event_full_n:
            full = _best_of(repeats,
                            lambda: event_refine(ks, GTX580, "event_full"))
        for rec in filter(None, (full, delta)):
            ratio = (rec["moves_per_s"] / full["moves_per_s"]
                     if full is not None and rec is delta else "")
            print_fn(f"gpu_mix,{n},{rec['path']},{rec['wall_s']:.4f},"
                     f"{rec['refine_evals']},{rec['moves_per_s']:.1f},"
                     f"{ratio if ratio == '' else f'{ratio:.1f}'}")
            results.append({"scenario": "gpu_mix", "n": n, **rec})
    print_fn("# Gated-DAG refine: full re-sim vs checkpoint delta "
             f"(budget {EVENT_BUDGET} full-sim equivalents, "
             "chain-structured edges)")
    print_fn("scenario,n,path,wall_s,evals,moves_per_s,throughput_ratio")
    for n in GATED_NS:
        rng = random.Random(seed)
        ks = gpu_mix(rng, n)
        edges = chain_edges(rng, n, width=max(4, n // 8))
        delta = _best_of(repeats, lambda: gated_refine(
            ks, edges, GTX580, "dag_refine_gated"))
        full = None
        if n <= max_gated_full_n:
            full = _best_of(repeats, lambda: gated_refine(
                ks, edges, GTX580, "dag_refine_gated_full"))
        for rec in filter(None, (full, delta)):
            ratio = (rec["moves_per_s"] / full["moves_per_s"]
                     if full is not None and rec is delta else "")
            print_fn(f"gpu_dag,{n},{rec['path']},{rec['wall_s']:.4f},"
                     f"{rec['refine_evals']},{rec['moves_per_s']:.1f},"
                     f"{ratio if ratio == '' else f'{ratio:.1f}'}")
            results.append({"scenario": "gpu_dag", "n": n, **rec})
    summary = _summary(results)
    out = {"benchmark": "scheduler_scaling",
           "refine_budget": REFINE_BUDGET,
           "event_refine_budget": EVENT_BUDGET,
           "ns": list(NS), "event_ns": list(EVENT_NS),
           "gated_ns": list(GATED_NS),
           "max_ref_n": max_ref_n,
           "max_event_full_n": max_event_full_n,
           "max_gated_full_n": max_gated_full_n,
           "repeats": repeats,
           "results": results, "summary": summary}
    print_fn(f"summary: {json.dumps(summary)}")
    return out


def _summary(results: list[dict]) -> dict:
    by = {(r["scenario"], r["n"], r["path"]): r for r in results}
    speedups = {}
    quality_ok = True
    for (scen, n, path), r in by.items():
        if path != "reference":
            continue
        f = by.get((scen, n, "fast"))
        if f is None:
            continue
        speedups[f"{scen}@n={n}"] = r["wall_s"] / f["wall_s"]
        if f["modelled_round_time_s"] > r["modelled_round_time_s"] * (1 + 1e-9):
            quality_ok = False
    s512 = {k: v for k, v in speedups.items() if k.endswith("n=512")}
    event_tp = {}
    for (scen, n, path), r in by.items():
        if path != "event_full":
            continue
        d = by.get((scen, n, "event_delta"))
        if d is not None:
            event_tp[f"{scen}@n={n}"] = (d["moves_per_s"] /
                                         max(r["moves_per_s"], 1e-9))
    tp256 = [v for k, v in event_tp.items() if k.endswith("n=256")]
    gated_tp = {}
    for (scen, n, path), r in by.items():
        if path != "dag_refine_gated_full":
            continue
        d = by.get((scen, n, "dag_refine_gated"))
        if d is not None:
            gated_tp[f"{scen}@n={n}"] = (d["moves_per_s"] /
                                         max(r["moves_per_s"], 1e-9))
    return {"speedups": speedups,
            "min_speedup_at_512": min(s512.values()) if s512 else None,
            "quality_no_worse_than_reference": quality_ok,
            "event_move_throughput_ratios": event_tp,
            "event_delta_throughput_at_256": tp256[0] if tp256 else None,
            "gated_move_throughput_ratios": gated_tp}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_scheduler_scaling.json")
    ap.add_argument("--max-ref-n", type=int, default=512)
    ap.add_argument("--max-event-full-n", type=int, default=256)
    ap.add_argument("--max-gated-full-n", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="run the reference path at every n")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=2,
                    help="best-of-k wall times for the guarded cells")
    args = ap.parse_args(argv)
    max_ref = max(NS) if args.full else args.max_ref_n
    out = run(max_ref_n=max_ref, seed=args.seed,
              max_event_full_n=args.max_event_full_n,
              max_gated_full_n=args.max_gated_full_n,
              repeats=args.repeats)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
