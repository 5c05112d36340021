"""Property tests for ``repro.obs`` (PR 8): trace identity (recorder
on vs off is bit-identical on modelled floats and served tokens
across the round / event / gated models, the fast refiner twins,
slicing, and the serving engine), trace conservation (per-unit span
interval unions equal the dispatcher's independently accumulated busy
time; resident blocks never exceed what the device caps admit), valid
Chrome-trace-event JSON structure, and the MetricsRegistry /
ScheduleCache counter-migration surface.

Written with plain ``random`` (no hypothesis dependency in the pinned
toolchain) over seeded draws, so failures reproduce exactly.
"""

import json
import math
import random
import re

import pytest

from repro.core import (GTX580, EventSimulator, KernelProfile,
                        RoundSimulator, refine_order)
from repro.core.refine import (DeltaEvaluator, _FastEventSim,
                               _FastRoundSim)
from repro.core.resources import (bs_kernel, ep_kernel, es_kernel,
                                  sw_kernel)
from repro.core.tpu import (decode_profile, make_serving_device,
                            prefill_profile)
from repro.graph.delta import _FastGatedSim
from repro.graph.streams import DagEventSimulator
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       PHASES, FlightRecorder, ScheduleTrace,
                       parse_prometheus_text, phase_breakdown,
                       prometheus_text)
from repro.serve.cache import ScheduleCache
from repro.slice import SlicePolicy, greedy_order_slices

_FAMS = [ep_kernel, bs_kernel, es_kernel, sw_kernel]
_TPU = make_serving_device()
_TPU4 = make_serving_device(n_units=4)

#: relative tolerance for busy-time vs span-union conservation: both
#: are sums of the same float dts in different orders
_CONS_RTOL = 1e-9


def _gpu_kernels(rng: random.Random, n: int) -> list[KernelProfile]:
    return [rng.choice(_FAMS)(f"k{i}",
                              grid=rng.choice([8, 16, 32, 48, 64, 96]),
                              shm=rng.choice([0, 4096, 8192, 16384]),
                              inst=rng.uniform(1e6, 5e8))
            for i in range(n)]


def _tpu_profiles(rng: random.Random, n: int) -> list[KernelProfile]:
    out = []
    for i in range(n):
        if rng.random() < 0.4:
            out.append(prefill_profile(
                f"p{i}", n_params=7e9,
                seq_len=rng.choice([128, 512, 2048, 8192]),
                kv_bytes_per_token=131072).profile())
        else:
            out.append(decode_profile(
                f"d{i}", n_params=7e9, kv_len=rng.randint(1, 8192),
                kv_bytes_per_token=131072).profile())
    return out


def _random_dag_edges(rng: random.Random, n: int,
                      density: float = 1.0) -> set:
    """Random forward edges (u < v): acyclic by construction."""
    edges = set()
    for _ in range(int(density * n)):
        u, v = rng.randrange(n), rng.randrange(n)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return edges


def _assert_conserved(tr: ScheduleTrace) -> None:
    """Per-unit span interval union == independently accumulated busy
    time: spans exactly tile the modelled residency."""
    assert tr.spans, "trace recorded no spans"
    for u in tr.units():
        union, busy = tr.span_union(u), tr.busy_of(u)
        assert math.isclose(union, busy, rel_tol=_CONS_RTOL,
                            abs_tol=1e-15), (u, union, busy)


# --------------------------------------------------------------------------
# trace identity: recorder on vs off is bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("device,maker", [(GTX580, _gpu_kernels),
                                          (_TPU, _tpu_profiles),
                                          (_TPU4, _tpu_profiles)])
def test_event_trace_identity_and_conservation(device, maker):
    rng = random.Random(5)
    for trial in range(8):
        ks = maker(rng, rng.randint(2, 24))
        t_plain = EventSimulator(device).simulate(ks)
        tr = ScheduleTrace()
        t_traced = EventSimulator(device).simulate(ks, trace=tr)
        assert t_traced == t_plain, trial
        assert tr.makespan == pytest.approx(t_plain, rel=1e-12)
        _assert_conserved(tr)
        # the fast twin emits the identical trace
        tr2 = ScheduleTrace()
        t_fast, _ = _FastEventSim(device).simulate(ks, trace=tr2)
        assert t_fast == t_plain
        assert tr2.spans == tr.spans


@pytest.mark.parametrize("device,maker", [(GTX580, _gpu_kernels),
                                          (_TPU, _tpu_profiles)])
def test_round_trace_identity(device, maker):
    rng = random.Random(7)
    for trial in range(8):
        ks = maker(rng, rng.randint(2, 20))
        t_plain = RoundSimulator(device).simulate(ks)
        tr = ScheduleTrace()
        assert RoundSimulator(device).simulate(ks, trace=tr) == t_plain
        # strict rounds: everything lands on unit 0, busy == makespan,
        # and a round-boundary instant closes every round
        assert tr.units() == [0]
        assert tr.busy_of(0) == pytest.approx(t_plain, rel=1e-12)
        rounds = [i for i in tr.instants if i[3] == "round"]
        assert rounds and rounds[-1][1] == pytest.approx(t_plain)
        tr2 = ScheduleTrace()
        t_fast, _ = _FastRoundSim(device).simulate(ks, trace=tr2)
        assert t_fast == t_plain
        assert tr2.spans == tr.spans


@pytest.mark.parametrize("device", [GTX580, _TPU4])
def test_gated_trace_identity_and_conservation(device):
    rng = random.Random(11)
    for trial in range(8):
        n = rng.randint(4, 24)
        ks = (_gpu_kernels(rng, n) if device is GTX580
              else _tpu_profiles(rng, n))
        eids = {(id(ks[u]), id(ks[v]))
                for u, v in _random_dag_edges(rng, n,
                                              rng.uniform(0.5, 2.0))}
        t_plain = DagEventSimulator(device, eids).simulate(ks)
        tr = ScheduleTrace()
        t_traced = DagEventSimulator(device, eids).simulate(ks,
                                                            trace=tr)
        assert t_traced == t_plain, trial
        _assert_conserved(tr)
        tr2 = ScheduleTrace()
        t_fast, _ = _FastGatedSim(device, eids).simulate(ks, trace=tr2)
        assert t_fast == t_plain
        assert tr2.spans == tr.spans


def test_sliced_trace_identity_and_conservation():
    rng = random.Random(13)
    for trial in range(6):
        n = rng.randint(4, 14)
        profs = []
        for i in range(n):
            if rng.random() < 0.4:    # oversized: forces slicing
                profs.append(prefill_profile(
                    f"r{i}:p:L0:attn", n_params=7e9,
                    seq_len=rng.choice([6144, 8192, 12288]),
                    kv_bytes_per_token=131072).profile())
            else:
                profs.append(decode_profile(
                    f"r{i}:d:L0:attn", n_params=7e9,
                    kv_len=rng.randint(256, 8192),
                    kv_bytes_per_token=131072).profile())
        edges = _random_dag_edges(rng, n, rng.uniform(0.0, 1.0))
        res = greedy_order_slices(profs, _TPU4, edges=edges,
                                  policy=SlicePolicy())
        eids = res.edges_by_id()
        t_plain = DagEventSimulator(_TPU4, eids).simulate(res.order)
        tr = ScheduleTrace()
        assert DagEventSimulator(_TPU4, eids).simulate(
            res.order, trace=tr) == t_plain, trial
        _assert_conserved(tr)
        if res.sliced:
            # zero-work joins retire as device-scoped instants, never
            # as spans (they hold no residency)
            joins = [i for i in tr.instants if i[3] == "join"]
            assert joins and all(i[2] is None for i in joins)
            assert not any("#join" in s[1] for s in tr.spans)


def test_delta_evaluator_rebase_forwards_trace():
    rng = random.Random(17)
    ks = _gpu_kernels(rng, 12)
    for model in ("round", "event"):
        ev = DeltaEvaluator(GTX580, model=model)
        t_plain = ev.rebase(ks)
        tr = ScheduleTrace()
        assert DeltaEvaluator(GTX580, model=model).rebase(
            ks, trace=tr) == t_plain
        assert tr.spans and tr.makespan == pytest.approx(t_plain)


def test_max_resident_blocks_within_device_caps():
    """Identical kernels with known per-block demands: the trace's
    peak concurrent residency per unit can never exceed what the unit
    caps admit."""
    rng = random.Random(19)
    for trial in range(6):
        ks = [ep_kernel(f"k{i}", grid=rng.choice([8, 16, 32]),
                        shm=8192, inst=2e7) for i in range(10)]
        dem = ks[0].demands
        cap_blocks = min(
            int(GTX580.cap(d) // v) for d, v in dem.items() if v > 0)
        tr = ScheduleTrace()
        EventSimulator(GTX580).simulate(ks, trace=tr)
        for u in tr.units():
            assert 1 <= tr.max_resident_blocks(u) <= cap_blocks


# --------------------------------------------------------------------------
# Chrome trace-event export
# --------------------------------------------------------------------------

def test_chrome_trace_structure_from_traced_arch():
    """The acceptance artifact: a DagEventSimulator run over a traced
    arch exports structurally valid Chrome trace-event JSON."""
    from repro.configs import get_config
    from repro.graph import greedy_order_dag, trace_arch

    cfg = get_config("qwen1.5-0.5b", "full")
    g = trace_arch(cfg, [("prefill", 128), ("decode", 256),
                         ("decode", 512)], max_stages=8).graph
    g.validate()
    sched = greedy_order_dag(g.kernels, _TPU4, edges=g.edges)
    tr = ScheduleTrace(label="traced-arch")
    t = DagEventSimulator(_TPU4, g.edges_by_id()).simulate(sched.order,
                                                           trace=tr)
    doc = tr.to_chrome()
    # round-trips through the JSON wire format
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs
    units = set(tr.units())
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["pid"] for m in metas} == units
    assert all(m["name"] == "process_name" for m in metas)
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(tr.spans) == len(sched.order)
    for e in xs:
        assert e["pid"] in units and e["tid"] == 0
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert e["ts"] + e["dur"] <= t * 1e6 * (1 + 1e-9)
        assert e["args"]["blocks"] >= 1
    for e in (e for e in evs if e["ph"] == "i"):
        assert e["s"] in ("g", "t") and e["ts"] >= 0.0
    assert {e["ph"] for e in evs} <= {"M", "X", "i"}


def test_gantt_renders_every_unit():
    rng = random.Random(23)
    tr = ScheduleTrace(label="gantt")
    EventSimulator(_TPU4).simulate(_tpu_profiles(rng, 12), trace=tr)
    text = tr.gantt(width=40)
    assert "gantt" in text and "legend:" in text
    for u in tr.units():
        assert f"unit {u:>2} |" in text
    assert ScheduleTrace().gantt() == "(empty trace)"


def test_gantt_golden_fixed_schedule():
    """Exact rendering of a hand-built two-unit trace: symbols follow
    span insertion order, overlapping distinct kernels collapse to
    ``*``, the header carries the makespan and per-column unit label,
    and instants list below the legend."""
    tr = ScheduleTrace(label="g")
    tr.span(0, "a", 0.0, 1.0)
    tr.span(0, "b", 1.0, 2.0)
    tr.span(1, "c", 0.0, 2.0)
    tr.span(1, "d", 0.5, 1.0)
    tr.instant("round", 2.0)
    assert tr.gantt(width=8) == (
        "g  (makespan 2s, 1 col = 0.25s)\n"
        "unit  0 |aaaabbbb|\n"
        "unit  1 |cc**cccc|\n"
        "legend: a=a, b=b, c=c, d=d\n"
        "  @2s [device] round")


def test_gantt_width_clamping():
    """A zero-width span sitting exactly at the makespan still renders
    one cell, clamped inside the chart; every row is exactly the asked
    width regardless of rounding."""
    tr = ScheduleTrace(label="clamp")
    tr.span(0, "a", 0.0, 2.0)
    tr.span(0, "z", 2.0, 2.0)      # degenerate span at the right edge
    text = tr.gantt(width=8)
    row = next(ln for ln in text.splitlines() if ln.startswith("unit"))
    assert row == "unit  0 |aaaaaaa*|"
    for w in (1, 3, 72):
        for ln in tr.gantt(width=w).splitlines():
            if ln.startswith("unit"):
                assert len(ln) == len("unit  0 ||") + w


def test_gantt_empty_trace_and_instant_only():
    assert ScheduleTrace().gantt() == "(empty trace)"
    tr = ScheduleTrace()
    tr.instant("round", 1.0)       # events but no residency
    assert tr.gantt() == "(empty trace)"


# --------------------------------------------------------------------------
# MetricsRegistry
# --------------------------------------------------------------------------

def test_registry_labels_are_distinct_series():
    m = MetricsRegistry()
    m.counter("cache_hits", namespace="flat").inc(3)
    m.counter("cache_hits", namespace="dag").inc()
    snap = m.snapshot()
    assert snap["cache_hits{namespace=flat}"] == 3.0
    assert snap["cache_hits{namespace=dag}"] == 1.0
    # same name + labels resolves to the same object
    assert (m.counter("cache_hits", namespace="flat")
            is m.counter("cache_hits", namespace="flat"))


def test_registry_kind_mismatch_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    with pytest.raises(TypeError):
        m.histogram("x")


def test_histogram_snapshot_expansion_and_timer():
    m = MetricsRegistry()
    # empty histograms are schema-stable zeros
    m.histogram("phase_refine")
    snap = m.snapshot()
    assert snap["phase_refine.count"] == 0
    assert snap["phase_refine.min_s"] == 0.0
    h = m.histogram("phase_compose")
    for v in (0.25, 0.75):
        h.observe(v)
    snap = m.snapshot()
    assert snap["phase_compose.count"] == 2
    assert snap["phase_compose.total_s"] == pytest.approx(1.0)
    assert snap["phase_compose.mean_s"] == pytest.approx(0.5)
    assert snap["phase_compose.min_s"] == 0.25
    assert snap["phase_compose.max_s"] == 0.75
    with m.timer("phase_guard"):
        pass
    assert m.histogram("phase_guard").count == 1
    assert m.histogram("phase_guard").total >= 0.0


def test_registry_reset_is_prefix_scoped():
    m = MetricsRegistry()
    c = m.counter("cache_hits", namespace="flat")
    c.inc(5)
    m.histogram("phase_compose").observe(1.0)
    m.reset(prefix="cache_")
    assert c.value == 0.0                       # reference stays live
    assert m.histogram("phase_compose").count == 1
    m.reset()
    assert m.histogram("phase_compose").count == 0


def test_phase_breakdown_covers_all_phases():
    m = MetricsRegistry()
    m.histogram("phase_compose").observe(0.5)
    pb = phase_breakdown(m)
    assert set(pb) == set(PHASES)
    assert pb["compose"] == {"calls": 1, "total_s": 0.5, "mean_s": 0.5}
    assert pb["execute"]["calls"] == 0


def test_histogram_reservoir_quantiles():
    """PR 9: histograms keep a seeded fixed-size reservoir, so
    snapshots carry p50/p95/p99 without storing every observation.
    Under the reservoir size the quantiles are exact."""
    m = MetricsRegistry()
    h = m.histogram("request_latency_s")
    for v in range(1, 101):           # 1..100, well under the reservoir
        h.observe(float(v))
    snap = m.snapshot()
    assert snap["request_latency_s.p50_s"] == pytest.approx(50.0, abs=1.5)
    assert snap["request_latency_s.p95_s"] == pytest.approx(95.0, abs=1.5)
    assert snap["request_latency_s.p99_s"] == pytest.approx(99.0, abs=1.5)
    # pre-existing snapshot keys are unchanged by the satellite
    assert snap["request_latency_s.count"] == 100
    assert snap["request_latency_s.mean_s"] == pytest.approx(50.5)
    assert snap["request_latency_s.min_s"] == 1.0
    assert snap["request_latency_s.max_s"] == 100.0


def test_histogram_reservoir_is_deterministic():
    """Over-full reservoirs subsample with a per-series seeded RNG
    (crc32 of the name, not the salted ``hash``), so two registries
    fed the identical stream report identical quantiles — and so does
    the same registry after a reset."""
    def fill(h):
        for v in range(5000):
            h.observe((v * 37 % 5000) / 5000.0)

    a, b = MetricsRegistry(), MetricsRegistry()
    fill(a.histogram("phase_compose"))
    fill(b.histogram("phase_compose"))
    ka = {k: v for k, v in a.snapshot().items() if ".p" in k}
    kb = {k: v for k, v in b.snapshot().items() if ".p" in k}
    assert ka == kb and ka
    # quantiles of a uniform stream land near the ideal even once the
    # reservoir is subsampling 5000 >> 256 points
    assert ka["phase_compose.p50_s"] == pytest.approx(0.5, abs=0.1)
    a.reset()
    fill(a.histogram("phase_compose"))
    assert {k: v for k, v in a.snapshot().items() if ".p" in k} == ka


def test_metric_classes_standalone():
    c, g, h = Counter("c"), Gauge("g"), Histogram("h")
    c.inc(); c.inc(2.5)
    assert c.value == 3.5
    g.set(7)
    assert g.value == 7.0
    assert h.mean == 0.0
    h.observe(2.0)
    assert (h.count, h.total, h.vmin, h.vmax) == (1, 2.0, 2.0, 2.0)


# --------------------------------------------------------------------------
# refinement metrics on the delta-evaluated refiners (the composer path)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["round", "event"])
def test_refine_batched_populates_metrics(model):
    rng = random.Random(7)
    ks = _gpu_kernels(rng, 12)
    m = MetricsRegistry()
    best, best_t, evals = refine_order(
        ks, GTX580, model=model, budget=40, metrics=m)
    snap = m.snapshot()
    assert snap["refine_evals"] == evals >= 1
    assert snap["refine_cost"] >= 1.0
    assert snap["refine_score_s.count"] == 1
    assert snap["refine_score_s.total_s"] >= 0.0
    # metrics are purely additive: same trajectory with them off
    best2, best_t2, evals2 = refine_order(
        ks, GTX580, model=model, budget=40)
    assert best_t2 == best_t and evals2 == evals
    assert [k.name for k in best2] == [k.name for k in best]


def test_refine_dag_batched_gated_populates_metrics():
    from repro.graph import refine_order_dag

    rng = random.Random(11)
    n = 16
    ks = _gpu_kernels(rng, n)
    edges = _random_dag_edges(rng, n)
    edge_ids = {(id(ks[u]), id(ks[v])) for u, v in edges}
    m = MetricsRegistry()
    _, t, evals = refine_order_dag(
        ks, GTX580, edge_ids=edge_ids, model="gated", budget=20,
        metrics=m)
    snap = m.snapshot()
    assert snap["refine_evals"] == evals >= 1
    assert snap["refine_score_s.count"] == 1
    _, t2, evals2 = refine_order_dag(
        ks, GTX580, edge_ids=edge_ids, model="gated", budget=20)
    assert (t2, evals2) == (t, evals)


# --------------------------------------------------------------------------
# ScheduleCache on the registry (satellite: reset + namespace breakdown)
# --------------------------------------------------------------------------

def test_cache_namespace_breakdown_and_legacy_totals():
    c = ScheduleCache()
    c.lookup(("flat", "symbiotic", ("a",)), namespace="flat")   # miss
    c.store(("flat", "symbiotic", ("a",)), (("a",),))
    c.lookup(("flat", "symbiotic", ("a",)), namespace="flat")   # hit
    c.lookup(("dag", "symbiotic", ("b",)), namespace="dag")     # miss
    assert c.hits == 1 and c.misses == 2
    assert c.hit_breakdown() == {"flat": {"hits": 1, "misses": 1},
                                 "dag": {"hits": 0, "misses": 1}}
    st = c.stats()
    assert st["hits"] == 1 and st["misses"] == 2
    assert st["by_namespace"] == c.hit_breakdown()
    # legacy attribute surface still works (registry-backed)
    c.dag_hits += 1
    c.gated_sims_saved += 0.25
    assert c.stats()["dag_hits"] == 1
    assert c.stats()["gated_sims_saved"] == 0.25
    assert c.metrics.counter("cache_dag_hits").value == 1.0


def test_cache_reset_zeroes_own_series_only():
    m = MetricsRegistry()
    c = ScheduleCache(metrics=m)
    c.store(("flat", "symbiotic", ("a",)), (("a",),))
    c.lookup(("flat", "symbiotic", ("a",)), namespace="flat")
    c.incremental_joins += 2
    m.histogram("phase_compose").observe(1.0)   # engine-shared series
    c.reset()
    assert c.hits == c.misses == 0
    assert c.incremental_joins == 0
    assert c.stats()["entries"] == 0
    assert c.lookup(("flat", "symbiotic", ("a",)),
                    namespace="flat") is None   # store dropped
    assert m.histogram("phase_compose").count == 1   # survives
    # store=False keeps patterns while zeroing counters
    c.store(("flat", "symbiotic", ("b",)), (("b",),))
    c.lookup(("flat", "symbiotic", ("b",)), namespace="flat")
    c.reset(store=False)
    assert c.hits == 0
    assert c.lookup(("flat", "symbiotic", ("b",)),
                    namespace="flat") is not None


# --------------------------------------------------------------------------
# serving engine: full instrumentation is invisible to outputs
# --------------------------------------------------------------------------

def test_engine_instrumentation_bit_identical_and_phased():
    jax = pytest.importorskip("jax")
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, SchedulerPolicy, ServingEngine

    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)

    def run(metrics=None, trace=None):
        eng = ServingEngine(cfg, params, max_len=32,
                            policy=SchedulerPolicy(
                                kind="symbiotic", respect_deps=True),
                            metrics=metrics, trace=trace)
        rng = np.random.default_rng(0)
        eng.submit([Request(i, rng.integers(0, 128, size=4),
                            max_new_tokens=3) for i in range(2)])
        return eng.run()

    s_plain = run()
    m, tr = MetricsRegistry(), ScheduleTrace()
    s_inst = run(metrics=m, trace=tr)
    assert s_inst["outputs"] == s_plain["outputs"]
    assert s_inst["total_new_tokens"] == s_plain["total_new_tokens"]
    assert s_inst["modelled_time_s"] == s_plain["modelled_time_s"]
    # phases and the snapshot ride on run() stats
    pb = s_inst["phases"]
    assert pb["compose"]["calls"] > 0 and pb["execute"]["calls"] > 0
    assert s_inst["metrics"]["engine_steps"] >= pb["compose"]["calls"]
    # the served-round trace spans the engine's modelled timeline
    assert tr.spans
    assert tr.makespan == pytest.approx(s_inst["modelled_time_s"],
                                        rel=1e-9)


#: every refine route the composer keeps: (respect_deps, refine_model,
#: extra policy fields)
_REFINE_ROUTES = {
    "flat-rounds": (False, "rounds", {}),
    "flat-round": (False, "round", {}),
    "flat-event": (False, "event", {}),
    "dag-round": (True, "round", {}),
    "dag-event": (True, "event", {}),
    "dag-gated": (True, "gated", {}),
    "dag-gated-sliced": (True, "gated", {"slice_policy": SlicePolicy(),
                                         "dag_guard": "gated"}),
}


def _run_refine_engine(policy):
    jax = pytest.importorskip("jax")
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, ServingEngine

    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=32, policy=policy)
    rng = np.random.default_rng(0)
    eng.submit([Request(i, rng.integers(0, 128, size=4),
                        max_new_tokens=3) for i in range(2)])
    return eng.run()


@pytest.mark.parametrize("route", list(_REFINE_ROUTES))
def test_engine_batched_refine_backend_records_metrics(route):
    """The composer hands ``cache.metrics`` to every refinement call:
    each refine route records its evaluations end to end, and the
    tokens match arrival-order (fifo) serving."""
    from repro.serve import SchedulerPolicy

    deps, model, extra = _REFINE_ROUTES[route]
    stats = _run_refine_engine(SchedulerPolicy(
        kind="refined", refine_model=model, refine_budget=20,
        respect_deps=deps, **extra))
    fifo = _run_refine_engine(SchedulerPolicy(kind="fifo"))
    assert stats["total_new_tokens"] > 0
    assert stats["outputs"] == fifo["outputs"]
    snap = stats["metrics"]
    assert snap["refine_evals"] >= 1
    assert snap["refine_score_s.count"] >= 1


# --------------------------------------------------------------------------
# export layer (PR 9): Prometheus exposition + JSONL flight recorder
# --------------------------------------------------------------------------

def _random_registry(rng: random.Random) -> MetricsRegistry:
    m = MetricsRegistry()
    for i in range(rng.randint(1, 5)):
        m.counter("cache_hits", namespace=rng.choice(["flat", "dag"])
                  ).inc(rng.randint(0, 50))
    m.counter("engine_steps").inc(rng.randint(1, 9))
    m.gauge("cache_entries").set(rng.uniform(0, 100))
    h = m.histogram("phase_compose")
    for _ in range(rng.randint(1, 40)):
        h.observe(rng.uniform(1e-6, 2.0))
    m.histogram("audit_quality_percentile",
                arch="qwen1.5-0.5b", kind="refined").observe(
                    rng.uniform(0, 100))
    return m


def test_prometheus_roundtrip_property():
    """Seeded property: every counter/gauge sample and every histogram
    sum/count survive the text exposition bit-exactly (%.17g), and
    quantile samples match the reservoir's answer."""
    rng = random.Random(29)
    for _ in range(10):
        m = _random_registry(rng)
        text = prometheus_text(m)
        parsed = parse_prometheus_text(text)
        snap = m.snapshot()
        for key, v in snap.items():
            name, _, field = key.partition(".")
            if not field:                       # counter / gauge
                # snapshot key {k=v} -> exposition key {k="v"}
                pk = "repro_" + re.sub(r"=([^,}]*)", r'="\1"', name)
                assert parsed[pk] == v, key
        h = m.histogram("phase_compose")
        assert parsed["repro_phase_compose_count"] == h.count
        assert parsed["repro_phase_compose_sum"] == pytest.approx(
            h.total, rel=1e-15)
        assert parsed['repro_phase_compose{quantile="0.5"}'] == \
            h.quantile(0.5)


def test_prometheus_text_structure():
    m = MetricsRegistry()
    m.counter("cache_hits", namespace="flat").inc(3)
    m.gauge("cache_entries").set(2)
    m.histogram("phase_compose").observe(0.5)
    text = prometheus_text(m)
    assert "# TYPE repro_cache_hits counter" in text
    assert "# TYPE repro_cache_entries gauge" in text
    assert "# TYPE repro_phase_compose summary" in text
    assert 'repro_cache_hits{namespace="flat"} 3' in text
    assert "repro_phase_compose_count 1" in text
    # one TYPE header per base metric, even with several labelled series
    m.counter("cache_hits", namespace="dag").inc()
    text = prometheus_text(m)
    assert text.count("# TYPE repro_cache_hits counter") == 1


def test_flight_recorder_roundtrip_and_timeline(tmp_path):
    rng = random.Random(31)
    rec = FlightRecorder()
    kinds = ("schedule", "cache", "audit", "rebuild")
    want = []
    for i in range(rng.randint(5, 40)):
        kind = rng.choice(kinds)
        fields = {"step": i, "ok": rng.random() < 0.5,
                  "ratio": rng.uniform(0, 2)}
        rec.event(kind, **fields)
        want.append({"seq": i, "kind": kind, **fields})
    assert rec.events == want
    # text round-trip
    assert FlightRecorder.load(rec.to_jsonl()) == want
    # file round-trip
    p = tmp_path / "flight.jsonl"
    rec.dump(str(p))
    assert FlightRecorder.load(str(p)) == want
    tl = FlightRecorder.timeline(want)
    assert tl["n_events"] == len(want)
    assert sum(tl["by_kind"].values()) == len(want)
    assert len(tl["lines"]) == len(want)
    assert tl["lines"][0].startswith("#0 ")


def test_flight_recorder_caps_events():
    rec = FlightRecorder(max_events=10)
    for i in range(25):
        rec.event("schedule", step=i)
    assert len(rec.events) == 10
    assert rec.dropped == 15
    assert rec.events[0]["step"] == 15      # FIFO drop, newest kept
    assert rec.events[-1]["seq"] == 24      # seq keeps counting
