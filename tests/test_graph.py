"""Property tests for the DAG scheduling subsystem (repro.graph):

* degenerate DAG: ``greedy_order_dag`` with an empty edge set is
  round-for-round identical to ``greedy_order_fast`` (the ISSUE-3
  acceptance pin), and ``DagEventSimulator`` with no edges is
  float-for-float equal to the reference ``EventSimulator``;
* every order emitted by the constrained greedy, the precedence-
  respecting refiner and the random-topological sampler is a valid
  topological order under randomized DAGs;
* ``trace_arch`` structure: per-request chains, cross-request
  independence, parameter-share normalisation;
* stream assignment partitions the schedule and pins chains;
* the gated simulator orders dependent work strictly after its
  predecessors (monotone vs the ungated bound) and rejects
  non-topological launch orders.

Plain ``random`` over seeded draws (no hypothesis in the pinned
toolchain), as in ``tests/test_fastscore.py``.
"""

import random

import pytest

from repro.configs import get_config
from repro.core import GTX580, EventSimulator, greedy_order_fast
from repro.core.resources import bs_kernel, ep_kernel, es_kernel, sw_kernel
from repro.core.tpu import (decode_profile, make_serving_device,
                            prefill_profile)
from repro.graph import (DagEventSimulator, KernelGraph, assign_streams,
                         fifo_rounds_dag, greedy_order_dag,
                         refine_order_dag, trace_arch)

_FAMS = [ep_kernel, bs_kernel, es_kernel, sw_kernel]
_TPU = make_serving_device()


def _gpu_kernels(rng: random.Random, n: int):
    return [rng.choice(_FAMS)(f"k{i}",
                              grid=rng.choice([8, 16, 32, 48, 64, 96]),
                              shm=rng.choice([0, 4096, 8192, 16384, 24576]),
                              inst=rng.uniform(1e6, 5e8))
            for i in range(n)]


def _tpu_profiles(rng: random.Random, n: int):
    items = []
    for i in range(n):
        if rng.random() < 0.4:
            items.append(prefill_profile(
                f"p{i}", n_params=7e9,
                seq_len=rng.choice([128, 256, 512, 1024]),
                kv_bytes_per_token=131072))
        else:
            items.append(decode_profile(
                f"d{i}", n_params=7e9, kv_len=rng.randint(1, 8192),
                kv_bytes_per_token=131072))
    return [it.profile() for it in items]


def _random_dag_edges(rng: random.Random, n: int,
                      density: float = 1.0) -> set:
    """Random forward edges (u < v): acyclic by construction."""
    edges = set()
    for _ in range(int(density * n)):
        u, v = rng.randrange(n), rng.randrange(n)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return edges


def _round_names(sched):
    return [rd.names for rd in sched.rounds]


# --------------------------------------------------------------------------
# degenerate DAG == unconstrained fast path
# --------------------------------------------------------------------------

def test_zero_edge_dag_reproduces_fast_greedy():
    """ISSUE-3 acceptance pin: >= 40 randomized kernel sets across
    both device families, empty edge set, identical rounds AND
    intra-round order."""
    rng = random.Random(42)
    checked = 0
    for trial in range(50):
        if trial % 2 == 0:
            ks, dev = _gpu_kernels(rng, rng.randint(1, 24)), GTX580
        else:
            ks, dev = _tpu_profiles(rng, rng.randint(1, 32)), _TPU
        ref = _round_names(greedy_order_fast(ks, dev))
        dag = _round_names(greedy_order_dag(ks, dev))
        assert ref == dag, f"trial {trial}: {ref} != {dag}"
        checked += 1
    assert checked >= 40


def test_zero_edge_gated_simulator_is_exact():
    """DagEventSimulator with no edges replays EventSimulator's float
    accumulation exactly."""
    rng = random.Random(7)
    for _ in range(20):
        ks = _gpu_kernels(rng, rng.randint(2, 16))
        t_ref = EventSimulator(GTX580).simulate(ks)
        t_dag = DagEventSimulator(GTX580, set()).simulate(ks)
        assert t_dag == t_ref


def test_empty_and_singleton_graphs():
    assert greedy_order_dag([], GTX580).rounds == []
    k = ep_kernel("only")
    assert _round_names(greedy_order_dag([k], GTX580)) == [["only"]]
    g = KernelGraph([k])
    g.validate()
    assert g.is_topological([k])


# --------------------------------------------------------------------------
# topological validity under random DAGs
# --------------------------------------------------------------------------

def test_dag_greedy_emits_topological_orders():
    rng = random.Random(3)
    for trial in range(40):
        n = rng.randint(2, 28)
        ks = _gpu_kernels(rng, n)
        edges = _random_dag_edges(rng, n, density=rng.uniform(0.0, 2.0))
        g = KernelGraph(ks, edges)
        g.validate()
        sched = greedy_order_dag(ks, GTX580, edges=edges)
        assert g.is_topological(sched.order), trial
        # no round may contain both ends of an edge (members run
        # concurrently; a dependent kernel waits for the next round)
        eids = g.edges_by_id()
        for rd in sched.rounds:
            ids = [id(k) for k in rd.kernels]
            assert not any((a, b) in eids for a in ids for b in ids)


def test_random_topological_orders_are_topological():
    rng = random.Random(11)
    for _ in range(10):
        n = rng.randint(3, 20)
        g = KernelGraph(_gpu_kernels(rng, n),
                        _random_dag_edges(rng, n, 1.5))
        for o in g.random_topological_orders(10, seed=rng.randrange(99)):
            assert g.is_topological(o)


def test_cycle_detection():
    ks = _gpu_kernels(random.Random(0), 3)
    g = KernelGraph(ks, {(0, 1), (1, 2)})
    g.validate()
    g.add_edge(2, 0)
    with pytest.raises(ValueError):
        g.validate()
    with pytest.raises(ValueError):
        greedy_order_dag(ks, GTX580, edges={(0, 1), (1, 2), (2, 0)})
    with pytest.raises(ValueError):
        g.random_topological_order(random.Random(0))


def test_refine_order_dag_stays_topological_and_no_worse():
    rng = random.Random(9)
    for _ in range(10):
        n = rng.randint(4, 16)
        ks = _gpu_kernels(rng, n)
        edges = _random_dag_edges(rng, n, 1.0)
        g = KernelGraph(ks, edges)
        sched = greedy_order_dag(ks, GTX580, edges=edges)
        from repro.core import simulate
        t0 = simulate(sched.order, GTX580, model="event")
        order, t, _ = refine_order_dag(sched.order, GTX580,
                                       edge_ids=g.edges_by_id(),
                                       budget=60, model="event",
                                       neighborhood="adjacent")
        assert g.is_topological(order)
        assert t <= t0 + 1e-15
        assert t == simulate(order, GTX580, model="event")


def test_refine_order_dag_rejects_illegal_input():
    ks = _gpu_kernels(random.Random(1), 4)
    with pytest.raises(ValueError):
        refine_order_dag([ks[1], ks[0], ks[2], ks[3]], GTX580,
                         edges={(0, 1)},
                         edge_ids={(id(ks[0]), id(ks[1]))})


# --------------------------------------------------------------------------
# gated simulator semantics
# --------------------------------------------------------------------------

def test_gated_simulator_serializes_a_full_chain():
    """A single dependency chain admits one kernel at a time, so the
    gated makespan is the sum of the kernels' solo event times (up to
    float re-association of the running clock).  Note the gate is NOT
    monotone versus the ungated dispatcher in general — delaying an
    admission changes co-residency and occupancy, which can help or
    hurt (the paper's order-sensitivity), so only full serialization
    has a closed form to pin."""
    rng = random.Random(13)
    sim = EventSimulator(GTX580)
    for _ in range(10):
        n = rng.randint(2, 10)
        ks = _gpu_kernels(rng, n)
        edges = {(i, i + 1) for i in range(n - 1)}
        g = KernelGraph(ks, edges)
        t_gated = DagEventSimulator(GTX580, g.edges_by_id()).simulate(ks)
        t_solo = sum(sim.simulate([k]) for k in ks)
        assert t_gated == pytest.approx(t_solo, rel=1e-9)


def test_gated_simulator_rejects_non_topological_order():
    ks = _gpu_kernels(random.Random(2), 2)
    sim = DagEventSimulator(GTX580, {(id(ks[0]), id(ks[1]))})
    with pytest.raises(ValueError):
        sim.simulate([ks[1], ks[0]])


def test_fifo_rounds_dag_respects_edges():
    rng = random.Random(17)
    for _ in range(10):
        n = rng.randint(3, 20)
        ks = _gpu_kernels(rng, n)
        edges = _random_dag_edges(rng, n, 1.0)
        g = KernelGraph(ks, edges)
        order = g.random_topological_order(rng)
        rounds = fifo_rounds_dag(order, GTX580, g.edges_by_id(),
                                 demands_of=lambda k: k.demands)
        assert [k for rd in rounds for k in rd] == order
        done: set[int] = set()
        for rd in rounds:
            ids = {id(k) for k in rd}
            for u, v in g.edges_by_id():
                if v in ids:
                    assert u in done, "pred must retire in an earlier round"
                    assert u not in ids
            done |= ids


# --------------------------------------------------------------------------
# trace_arch structure
# --------------------------------------------------------------------------

def test_trace_arch_chains_and_independence():
    cfg = get_config("qwen1.5-0.5b", "smoke")
    reqs = [("prefill", 128), ("decode", 512), ("decode", 1024)]
    tw = trace_arch(cfg, reqs)
    tw.graph.validate()
    # every request owns one chain: len(chain)-1 edges, no cross edges
    per_req: dict[int, list[int]] = {}
    for i, o in enumerate(tw.owners):
        per_req.setdefault(o, []).append(i)
    n_edges = sum(len(v) - 1 for v in per_req.values())
    assert len(tw.graph.edges) == n_edges
    for u, v in tw.graph.edges:
        assert tw.owners[u] == tw.owners[v]
        assert u < v
    # tail items close their chains
    for rid, idxs in per_req.items():
        assert tw.tail_of[rid] == max(idxs)
    # attention stages carry the KV traffic, ffn stages don't
    for it in tw.items:
        if ":attn" in it.name:
            assert it.hbm_bytes > 0.0 or ":p:" not in it.name


def test_trace_arch_param_share_normalisation():
    cfg = get_config("mixtral-8x7b", "smoke")
    n_params = 1e9
    tw = trace_arch(cfg, [("prefill", 64)], n_params=n_params)
    # prefill touches the full expert banks: shares sum to the model
    # minus the (untraced) embedding tables
    flops_total = sum(it.flops for it in tw.items)
    assert flops_total < 2.0 * n_params * 64
    assert flops_total > 0.5 * 2.0 * n_params * 64
    # decode streams only routed-active experts: strictly fewer flops
    twd = trace_arch(cfg, [("decode", 64)], n_params=n_params)
    moe_p = [it for it in tw.items if ":moe" in it.name]
    moe_d = [it for it in twd.items if ":moe" in it.name]
    assert moe_p and moe_d
    assert (sum(it.flops for it in moe_d) <
            sum(it.flops for it in moe_p) / 64 * 1.01)


def test_trace_arch_max_stages_coarsening():
    cfg = get_config("qwen1.5-0.5b", "full")   # 24 layers -> 48 stages
    fine = trace_arch(cfg, [("decode", 256)])
    coarse = trace_arch(cfg, [("decode", 256)], max_stages=6)
    assert len(coarse.items) <= 6 < len(fine.items)
    # grouping preserves total work and traffic
    assert sum(i.flops for i in coarse.items) == pytest.approx(
        sum(i.flops for i in fine.items), rel=1e-9)
    assert sum(i.hbm_bytes for i in coarse.items) == pytest.approx(
        sum(i.hbm_bytes for i in fine.items), rel=1e-9)
    coarse.graph.validate()


# --------------------------------------------------------------------------
# stream assignment
# --------------------------------------------------------------------------

def test_assign_streams_partitions_and_pins_chains():
    cfg = get_config("qwen1.5-0.5b", "smoke")
    tw = trace_arch(cfg)
    g = tw.graph
    sched = greedy_order_dag(g.kernels, _TPU, edges=g.edges)
    sa = assign_streams(sched, g.edges_by_id(), k=3)
    # partition: every kernel on exactly one queue
    all_ids = sorted(id(k) for s in sa.streams for k in s)
    assert all_ids == sorted(id(k) for k in g.kernels)
    # chains pin: both ends of every edge share a queue
    for u, v in g.edges:
        assert (sa.stream_of[id(g.kernels[u])]
                == sa.stream_of[id(g.kernels[v])])
    # independent roots spread: >1 queue used when k > 1
    assert len({sa.stream_of[id(k)] for k in g.kernels}) > 1
    with pytest.raises(ValueError):
        assign_streams(sched, g.edges_by_id(), k=0)


# --------------------------------------------------------------------------
# slow sweep (ISSUE-3 CI satellite)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_dag_greedy_n512_sweep():
    """n=512 chain-structured DAG: construction completes, emits a
    valid topological order, and the 0-edge variant still matches the
    flat fast path at this scale."""
    rng = random.Random(29)
    ks = _gpu_kernels(rng, 512)
    edges = set()
    chains: list[list[int]] = [[] for _ in range(64)]
    for i in range(512):
        c = chains[rng.randrange(64)]
        if c:
            edges.add((c[-1], i))
        c.append(i)
    g = KernelGraph(ks, edges)
    sched = greedy_order_dag(ks, GTX580, edges=edges)
    assert g.is_topological(sched.order)
    assert _round_names(greedy_order_dag(ks, GTX580)) == \
        _round_names(greedy_order_fast(ks, GTX580))


def test_ffn_params_price_the_held_share():
    """A decode's stream counts the routed experts a token is expected
    to reach among those held here, the shared experts and the router;
    a layer holding every expert prices its ``top_k`` as before."""
    from repro.configs import get_config
    from repro.graph.kernel_graph import _ffn_params
    full = get_config("deepseek-v2-236b")
    share = full.replace(experts_held=20)
    expert, router = 3.0 * 5120 * 1536, 5120 * 160
    assert _ffn_params(full, 1, active=True) == (6 + 2) * expert + router
    assert _ffn_params(share, 1, active=True) == \
        (6 * 20 / 160 + 2) * expert + router
    assert _ffn_params(share, 1, active=False) == (20 + 2) * expert + router
    assert _ffn_params(share, 0, active=True) == 3.0 * 5120 * 12288
