"""Property tests for the kernel slicing subsystem (repro.slice):

* slice-factor-1 identity: with no policy (or one that never
  triggers) the sliced pipeline reproduces the unsliced DAG pipeline
  bit-for-bit — same rounds, same order, identical gated makespan;
* slice-profile conservation: slices sum back to the parent (work,
  traffic, demand mass, tokens) within float tolerance, while the
  stage weight stream is copied, not split;
* topological validity of slice/join expansion under random DAGs:
  slices inherit in-edges, successors hang off the join, sibling
  slices stay mutually independent, and every emitted order is
  topological;
* sliced makespan <= unsliced makespan on saturating (oversized-slot)
  profiles in the gated simulator;
* zero-work join markers retire instantly in ``DagEventSimulator``;
* serving: generated tokens are bit-identical with ``slice_policy``
  on or off, and the DAG-path ScheduleCache warms up.

Plain ``random`` over seeded draws (no hypothesis in the pinned
toolchain), as in ``tests/test_fastscore.py`` / ``tests/test_graph.py``.
"""

import random
from dataclasses import replace

import pytest

from repro.core import GTX580
from repro.core.resources import bs_kernel, ep_kernel, es_kernel, sw_kernel
from repro.core.tpu import (decode_profile, make_serving_device,
                            prefill_profile)
from repro.graph import DagEventSimulator, KernelGraph, greedy_order_dag
from repro.slice import (KernelSlicer, SlicePolicy, coalesce_rounds,
                         expand_nodes, greedy_order_slices, is_join,
                         is_slice, join_item, join_profile,
                         merge_slice_profiles, parent_name,
                         refine_order_slices, slice_indices)

_TPU = make_serving_device()
_FAMS = [ep_kernel, bs_kernel, es_kernel, sw_kernel]


def _tpu_items(rng: random.Random, n: int, *, oversized_frac=0.25):
    items = []
    for i in range(n):
        if rng.random() < oversized_frac:
            items.append(prefill_profile(
                f"r{i}:p:L0:attn", n_params=7e9,
                seq_len=rng.choice([6144, 8192, 12288]),
                kv_bytes_per_token=131072))
        else:
            items.append(decode_profile(
                f"r{i}:d:L0:attn", n_params=7e9,
                kv_len=rng.randint(256, 8192),
                kv_bytes_per_token=131072))
    return items


def _gpu_kernels(rng: random.Random, n: int):
    return [rng.choice(_FAMS)(f"k{i}",
                              grid=rng.choice([8, 16, 32, 48, 64, 96]),
                              shm=rng.choice([0, 4096, 8192, 16384]),
                              inst=rng.uniform(1e6, 5e8))
            for i in range(n)]


def _random_dag_edges(rng: random.Random, n: int, density=1.0) -> set:
    edges = set()
    for _ in range(int(density * n)):
        u, v = rng.randrange(n), rng.randrange(n)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return edges


def _round_names(sched):
    return [rd.names for rd in sched.rounds]


# --------------------------------------------------------------------------
# naming / policy
# --------------------------------------------------------------------------

def test_name_helpers():
    assert parent_name("r0:p:L3:moe#s1of4") == "r0:p:L3:moe"
    assert parent_name("r0:p:L3:moe#join") == "r0:p:L3:moe"
    assert parent_name("r0:p:L3:moe") == "r0:p:L3:moe"
    assert is_slice("a#s0of2") and not is_slice("a#join")
    assert is_join("a#join") and not is_join("a#s0of2")


def test_policy_validation():
    with pytest.raises(ValueError):
        SlicePolicy(mode="nope")
    with pytest.raises(ValueError):
        SlicePolicy(target_fill=0.0)
    with pytest.raises(ValueError):
        SlicePolicy(fixed_k=0)


def test_slice_count_modes():
    sl_occ = KernelSlicer(SlicePolicy(), _TPU)
    sl_fill = KernelSlicer(SlicePolicy(mode="round_fill",
                                       target_fill=0.5), _TPU)
    sl_fix = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=5), _TPU)
    big = prefill_profile("r0:p:L0", n_params=7e9, seq_len=8192,
                          kv_bytes_per_token=131072).profile()
    small = decode_profile("r1:d:L0", n_params=7e9, kv_len=512,
                           kv_bytes_per_token=131072).profile()
    # footprint 2x the slot budget: oversized for every mode
    assert sl_occ.footprint_frac(big) == pytest.approx(2.0)
    assert sl_occ.slice_count(big) == 3      # ceil(2.0 / 0.75)
    assert sl_fill.slice_count(big) == 4     # ceil(2.0 / 0.5)
    assert sl_fix.slice_count(big) == 5
    # fits comfortably: occupancy / fixed leave it alone
    assert sl_occ.slice_count(small) == 1
    assert sl_fix.slice_count(small) == 1
    # slices and joins are terminal
    cut = sl_occ.slice_profile(big, 3)[0]
    assert sl_occ.slice_count(cut) == 1
    assert sl_occ.slice_count(join_profile(big)) == 1


def test_slice_count_clamps_to_granularity():
    sl = KernelSlicer(SlicePolicy(mode="fixed", trigger_frac=0.0,
                                  fixed_k=16), _TPU)
    one_tok = decode_profile("r0:d:L0", n_params=7e9, kv_len=4096,
                             kv_bytes_per_token=131072)
    assert len(sl.slice_item(one_tok, 16)) == 1   # 1 token: uncuttable
    four = prefill_profile("r1:p:L0", n_params=1e9, seq_len=4,
                           kv_bytes_per_token=131072)
    assert len(sl.slice_item(four, 16)) == 4


# --------------------------------------------------------------------------
# conservation
# --------------------------------------------------------------------------

def test_item_slices_conserve_parent():
    rng = random.Random(3)
    sl = KernelSlicer(SlicePolicy(), _TPU)
    for _ in range(30):
        it = prefill_profile(f"r0:p:L{rng.randrange(9)}",
                             n_params=rng.uniform(1e9, 3e11),
                             seq_len=rng.choice([4097, 6144, 8192, 16384]),
                             kv_bytes_per_token=rng.uniform(1e3, 2e5))
        it = replace(it, weight_bytes=2e9)
        k = rng.randint(2, 8)
        parts = sl.slice_item(it, k)
        assert len(parts) == k
        assert sum(p.flops for p in parts) == pytest.approx(it.flops)
        assert sum(p.hbm_bytes for p in parts) == pytest.approx(it.hbm_bytes)
        assert sum(p.vmem_bytes for p in parts) == pytest.approx(
            it.vmem_bytes)
        assert sum(p.tokens for p in parts) == it.tokens
        for p in parts:
            # the stage weight stream is shared, never split
            assert p.weight_bytes == it.weight_bytes
            assert p.intensity == pytest.approx(it.intensity)
            assert parent_name(p.name) == it.name


def test_profile_slices_conserve_parent():
    rng = random.Random(7)
    sl_gpu = KernelSlicer(SlicePolicy(), GTX580)
    for _ in range(30):
        prof = rng.choice(_FAMS)(f"k{rng.randrange(99)}",
                                 grid=rng.choice([16, 48, 96, 256]),
                                 shm=rng.choice([0, 8192, 16384]),
                                 inst=rng.uniform(1e6, 1e9))
        k = rng.randint(2, 6)
        parts = sl_gpu.slice_profile(prof, k)
        k_eff = min(k, prof.n_blocks)
        assert len(parts) == k_eff
        # grid partition: block counts sum, per-block profile unchanged
        assert sum(p.n_blocks for p in parts) == prof.n_blocks
        for p in parts:
            assert p.inst_per_block == prof.inst_per_block
            assert p.demands == prof.demands
            assert p.r == prof.r
        # total work / traffic / demand mass conserved
        assert sum(p.inst_per_block * p.n_blocks for p in parts) == \
            pytest.approx(prof.inst_per_block * prof.n_blocks)
        assert sum(p.mem_per_block() * p.n_blocks for p in parts) == \
            pytest.approx(prof.mem_per_block() * prof.n_blocks)


def test_single_block_profile_slices_scale_mass():
    sl = KernelSlicer(SlicePolicy(), _TPU)
    prof = prefill_profile("r0:p:L0", n_params=7e9, seq_len=8193,
                           kv_bytes_per_token=131072).profile()
    parts = sl.slice_profile(prof, 3)
    assert len(parts) == 3
    for dim in prof.demands:
        assert sum(p.demands[dim] for p in parts) == \
            pytest.approx(prof.demands[dim])
    assert sum(p.inst_per_block for p in parts) == \
        pytest.approx(prof.inst_per_block)
    assert all(p.r == prof.r for p in parts)


# --------------------------------------------------------------------------
# expansion topology
# --------------------------------------------------------------------------

def test_expand_nodes_rewires_the_diamond():
    rng = random.Random(11)
    sl = KernelSlicer(SlicePolicy(), GTX580)
    for _ in range(20):
        n = rng.randint(4, 20)
        ks = _gpu_kernels(rng, n)
        edges = _random_dag_edges(rng, n, 1.5)
        t = rng.randrange(n)
        parts = sl.slice_profile(ks[t], rng.randint(2, 4))
        if len(parts) < 2:
            continue
        exp = expand_nodes(ks, edges, {t: (parts, join_profile(ks[t]))})
        g = KernelGraph(exp.kernels, exp.edges)
        g.validate()                      # still acyclic
        slice_idx = set(exp.new_of[t])
        join_idx = exp.join_of[t]
        for u, v in edges:
            if v == t:                    # in-edges inherited by slices
                for s in slice_idx:
                    assert (exp.new_of[u][0], s) in exp.edges
            if u == t:                    # out-edges hang off the join
                assert (join_idx, exp.new_of[v][0]) in exp.edges
        for s in slice_idx:               # diamond closes through join
            assert (s, join_idx) in exp.edges
            # sibling slices are mutually independent
            for s2 in slice_idx:
                assert (s, s2) not in exp.edges
        assert all(exp.parent_of[s] == t for s in slice_idx)
        assert exp.parent_of[join_idx] == t


def test_expansion_preserves_topological_input_order():
    """Input with forward edges (u < v) stays forward after in-place
    expansion — the invariant the serving fifo baseline relies on."""
    rng = random.Random(13)
    sl = KernelSlicer(SlicePolicy(), GTX580)
    for _ in range(10):
        n = rng.randint(5, 16)
        ks = _gpu_kernels(rng, n)
        edges = _random_dag_edges(rng, n, 1.0)
        exps = {}
        for t in rng.sample(range(n), rng.randint(1, 3)):
            parts = sl.slice_profile(ks[t], 3)
            if len(parts) >= 2:
                exps[t] = (parts, join_profile(ks[t]))
        if not exps:
            continue
        exp = expand_nodes(ks, edges, exps)
        assert all(u < v for u, v in exp.edges)


def test_greedy_order_slices_emits_topological_orders():
    rng = random.Random(17)
    pol = SlicePolicy(mode="round_fill", target_fill=0.5)
    for _ in range(15):
        n = rng.randint(4, 20)
        items = _tpu_items(rng, n, oversized_frac=0.4)
        profs = [it.profile() for it in items]
        edges = _random_dag_edges(rng, n, rng.uniform(0.0, 1.5))
        res = greedy_order_slices(profs, _TPU, edges=edges, policy=pol)
        g = res.graph()
        g.validate()
        assert g.is_topological(res.order)
        # no round contains both ends of an edge
        eids = res.edges_by_id()
        for rd in res.rounds:
            ids = [id(k) for k in rd.kernels]
            assert not any((a, b) in eids for a in ids for b in ids)
        # parent_of maps every expanded node to an original index
        assert len(res.parent_of) == len(res.kernels)
        assert all(0 <= p < n for p in res.parent_of)


def test_refine_order_slices_respects_slice_edges():
    rng = random.Random(19)
    items = _tpu_items(rng, 10, oversized_frac=0.5)
    profs = [it.profile() for it in items]
    edges = {(i, i + 1) for i in range(0, 8, 2)}
    res = greedy_order_slices(profs, _TPU, edges=edges,
                              policy=SlicePolicy())
    assert res.sliced            # something was cut
    order, _, _ = refine_order_slices(res, _TPU, budget=30,
                                      model="event")
    assert res.graph().is_topological(order)


# --------------------------------------------------------------------------
# slice-factor-1 identity
# --------------------------------------------------------------------------

def test_factor1_identity_no_policy():
    """policy=None: identical rounds, order and gated makespan to the
    unsliced DAG pipeline, across randomized DAG workloads."""
    rng = random.Random(23)
    for _ in range(20):
        n = rng.randint(2, 24)
        items = _tpu_items(rng, n, oversized_frac=0.3)
        profs = [it.profile() for it in items]
        edges = _random_dag_edges(rng, n, 1.0)
        ref = greedy_order_dag(profs, _TPU, edges=edges)
        res = greedy_order_slices(profs, _TPU, edges=edges, policy=None)
        assert _round_names(res.schedule) == _round_names(ref)
        assert res.sliced == {} and res.passes == 0
        eids = KernelGraph(profs, edges).edges_by_id()
        t_ref = DagEventSimulator(_TPU, eids).simulate(ref.order)
        t_res = DagEventSimulator(_TPU, res.edges_by_id()).simulate(
            res.order)
        assert t_res == t_ref    # bit-identical float accumulation


def test_factor1_identity_untriggered_policy():
    """A policy whose trigger nothing crosses leaves the schedule
    bit-identical too (the lazy path never expands)."""
    rng = random.Random(29)
    for _ in range(10):
        n = rng.randint(2, 16)
        profs = [it.profile()
                 for it in _tpu_items(rng, n, oversized_frac=0.0)]
        edges = _random_dag_edges(rng, n, 0.8)
        ref = greedy_order_dag(profs, _TPU, edges=edges)
        res = greedy_order_slices(profs, _TPU, edges=edges,
                                  policy=SlicePolicy())
        assert _round_names(res.schedule) == _round_names(ref)
        assert res.passes == 0


# --------------------------------------------------------------------------
# gated simulator: joins + saturating profiles
# --------------------------------------------------------------------------

def test_join_markers_add_no_gated_time():
    """A slice diamond over one kernel simulates to the same gated
    time as the unsliced kernel when nothing else co-executes, and
    the zero-work join never inflates the makespan."""
    it = prefill_profile("r0:p:L0", n_params=7e9, seq_len=8192,
                         kv_bytes_per_token=131072)
    prof = it.profile()
    sl = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=2), _TPU)
    parts = sl.slice_profile(prof, 2)
    jn = join_profile(prof)
    exp = expand_nodes([prof], set(), {0: (parts, jn)})
    g = KernelGraph(exp.kernels, exp.edges)
    t_unsliced = DagEventSimulator(_TPU, set()).simulate([prof])
    t_sliced = DagEventSimulator(_TPU, g.edges_by_id()).simulate(
        exp.kernels)
    # two half-size oversized passes == one full pass (same roofline)
    assert t_sliced == pytest.approx(t_unsliced, rel=1e-9)


def test_sliced_makespan_no_worse_on_saturating_profiles():
    """ISSUE-4 pin: on profiles that saturate the slot budget, the
    sliced greedy's gated makespan is never worse than the unsliced
    greedy's, and strictly better when there is memory-bound work to
    co-execute."""
    rng = random.Random(31)
    strict_wins = 0
    for trial in range(12):
        n = rng.randint(6, 18)
        items = _tpu_items(rng, n, oversized_frac=0.35)
        if not any(it.tokens > 4096 for it in items):
            continue
        profs = [it.profile() for it in items]
        un = greedy_order_dag(profs, _TPU)
        t_un = DagEventSimulator(_TPU, set()).simulate(un.order)
        res = greedy_order_slices(profs, _TPU, policy=SlicePolicy())
        t_sl = DagEventSimulator(_TPU, res.edges_by_id()).simulate(
            res.order)
        assert t_sl <= t_un * (1 + 1e-9), trial
        if t_sl < t_un * (1 - 1e-6):
            strict_wins += 1
    assert strict_wins >= 3


def test_zero_work_join_requires_drained_predecessors():
    """The join is still gated: it must not retire before its slices,
    so successors of the join start strictly after every slice."""
    it = prefill_profile("r0:p:L0", n_params=7e9, seq_len=8192,
                         kv_bytes_per_token=131072)
    tail = decode_profile("r0:d:L1", n_params=7e9, kv_len=4096,
                          kv_bytes_per_token=131072).profile()
    prof = it.profile()
    sl = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=2), _TPU)
    parts = sl.slice_profile(prof, 2)
    exp = expand_nodes([prof, tail], {(0, 1)},
                       {0: (parts, join_profile(prof))})
    g = KernelGraph(exp.kernels, exp.edges)
    sim = DagEventSimulator(_TPU, g.edges_by_id())
    t_chain = sim.simulate(exp.kernels)
    solo = DagEventSimulator(_TPU, set())
    t_parts = solo.simulate(parts) + solo.simulate([tail])
    # fully serialized chain: slices then tail, join adding nothing
    assert t_chain == pytest.approx(t_parts, rel=1e-9)
    # a non-topological order (join before its slices) is rejected
    bad = [exp.kernels[i] for i in (exp.join_of[0], *exp.new_of[0])] + \
        [tail]
    with pytest.raises(ValueError):
        sim.simulate(bad)


# --------------------------------------------------------------------------
# serving integration
# --------------------------------------------------------------------------

def _smoke_engine(policy, device):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import ServingEngine
    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = _smoke_engine._params
    if params is None or _smoke_engine._cfg is not cfg:
        params = T.init(jax.random.PRNGKey(0), cfg)
        _smoke_engine._params, _smoke_engine._cfg = params, cfg
    return ServingEngine(cfg, params, max_len=64, policy=policy,
                         device=device)


_smoke_engine._params = None
_smoke_engine._cfg = None


def _smoke_requests(n=3, size=8):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, 512, size=size), max_new_tokens=4)
            for i in range(n)]


def test_serving_tokens_bit_identical_with_slice_policy():
    """slice_policy only reshapes modelled rounds: generated tokens
    are bit-identical with it on or off — against a shrunken slot
    budget that makes the 8-token prefill stages genuinely oversized,
    so slicing actually triggers."""
    from repro.serve import SchedulerPolicy
    dev = make_serving_device(token_budget=6)
    base = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                         respect_deps=True), dev)
    base.submit(_smoke_requests())
    s_base = base.run()
    sliced = _smoke_engine(
        SchedulerPolicy(kind="symbiotic", respect_deps=True,
                        slice_policy=SlicePolicy()), dev)
    sliced.submit(_smoke_requests())
    s_sliced = sliced.run()
    assert s_sliced["outputs"] == s_base["outputs"]
    assert all(len(v) >= 4 for v in s_sliced["outputs"].values())


def test_serving_gated_guard_token_identity():
    """The gated-event guard (``dag_guard="gated"``) only changes
    which *composition* wins the fifo comparison — generated tokens
    stay bit-identical to the round-guard engine, with slicing on
    (shrunken slot budget so cutting genuinely triggers) and off."""
    from repro.serve import SchedulerPolicy
    dev = make_serving_device(token_budget=6)
    base = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                         respect_deps=True,
                                         slice_policy=SlicePolicy()), dev)
    base.submit(_smoke_requests())
    s_base = base.run()
    gated = _smoke_engine(
        SchedulerPolicy(kind="symbiotic", respect_deps=True,
                        slice_policy=SlicePolicy(), dag_guard="gated"),
        dev)
    gated.submit(_smoke_requests())
    s_gated = gated.run()
    assert s_gated["outputs"] == s_base["outputs"]
    # unsliced path too
    plain = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                          respect_deps=True,
                                          dag_guard="gated"), dev)
    plain.submit(_smoke_requests())
    assert plain.run()["outputs"] == s_base["outputs"]


def test_serving_gated_guard_scores_sliced_composition():
    """``Composer.dag_gated_time`` rebuilds the expanded slice/join dependency
    structure from item names: finite on a composition whose first
    stage was cut into slices + join, and ``inf`` (guard rejection)
    when the flat order breaks the slice diamond (join launched before
    its slices)."""
    from repro.serve import SchedulerPolicy
    from repro.slice import KernelSlicer, join_item
    dev = make_serving_device(token_budget=6)
    eng = _smoke_engine(
        SchedulerPolicy(kind="symbiotic", respect_deps=True,
                        slice_policy=SlicePolicy(), dag_guard="gated",
                        cache=False), dev)
    eng.submit(_smoke_requests())
    triples, traced = eng._work_items_dag()
    # hand-cut the first request's head stage into a slice diamond,
    # exactly as compose_dag's make_slices closure would
    it0, r0, kind0 = triples[0]
    parts = KernelSlicer(SlicePolicy(mode="fixed", trigger_frac=0.0,
                                     fixed_k=2), dev).slice_item(it0, 2)
    assert len(parts) == 2
    ji = join_item(it0)
    rounds = ([[(parts[0], r0, "frag"), (parts[1], r0, "frag")],
               [(ji, r0, kind0)]] +
              [[trip] for trip in triples[1:]])
    t = eng.composer.dag_gated_time(rounds, traced)
    assert 0.0 < t < float("inf")
    # join before its slices: non-topological flat order scores inf
    bad = ([[(ji, r0, kind0)],
            [(parts[0], r0, "frag"), (parts[1], r0, "frag")]] +
           [[trip] for trip in triples[1:]])
    assert eng.composer.dag_gated_time(bad, traced) == float("inf")


def test_gated_guard_unlocks_slicing_win_round_guard_hides():
    """The ROADMAP slicing follow-up, resolved: on a prefill+decode
    mix whose prefill stages are oversized, the round-model guard
    structurally rejects the sliced composition (every slice round
    pays the stage weight stream) and serves dep-aware fifo, while
    the gated guard accepts it — and the accepted composition's gated
    makespan is strictly better than the round guard's choice."""
    import numpy as np
    from repro.serve import Request, SchedulerPolicy
    dev = make_serving_device(token_budget=6)

    def submit(eng):
        rng = np.random.default_rng(0)
        eng.submit([Request(i, rng.integers(0, 512, size=12),
                            max_new_tokens=4) for i in range(2)] +
                   [Request(10 + i, rng.integers(0, 512, size=2),
                            max_new_tokens=6) for i in range(6)])

    results = {}
    for guard in ("rounds", "gated"):
        eng = _smoke_engine(
            SchedulerPolicy(kind="symbiotic", respect_deps=True,
                            slice_policy=SlicePolicy(), dag_guard=guard,
                            cache=False), dev)
        submit(eng)
        triples, traced = eng._work_items_dag()
        rounds = eng.composer.compose_dag(triples, traced)
        names = [t[0].name for rd in rounds for t in rd]
        results[guard] = (sum(1 for nm in names if "#s" in nm),
                          eng.composer.dag_gated_time(rounds, traced))
    assert results["rounds"][0] == 0, "round guard serves unsliced fifo"
    assert results["gated"][0] > 0, "gated guard accepts the slices"
    assert results["gated"][1] < results["rounds"][1]


def test_serving_refine_model_gated_runs():
    """kind="refined" with refine_model="gated" threads the gated
    delta evaluator through compose_dag; tokens match the symbiotic
    engine (refinement only reorders modelled rounds)."""
    from repro.serve import SchedulerPolicy
    dev = make_serving_device()
    base = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                         respect_deps=True), dev)
    base.submit(_smoke_requests())
    s_base = base.run()
    ref = _smoke_engine(
        SchedulerPolicy(kind="refined", respect_deps=True,
                        refine_model="gated", refine_budget=20,
                        dag_guard="gated"), dev)
    ref.submit(_smoke_requests())
    s_ref = ref.run()
    assert s_ref["outputs"] == s_base["outputs"]


def test_serving_dag_cache_warms_up():
    """PR 3 bypassed the cache on the respect_deps path; the
    coarsened per-request chain keying must now produce hits in
    decode-heavy steady state, surfaced as ``dag_hits``."""
    from repro.serve import SchedulerPolicy
    eng = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                        respect_deps=True),
                        make_serving_device())
    eng.submit(_smoke_requests())
    stats = eng.run()["schedule_cache"]
    assert stats["dag_hits"] >= 1
    assert stats["hits"] == stats["dag_hits"]


def test_dag_replay_reproduces_cold_composition():
    """Replaying a cached DAG pattern on the identical queue state
    must reproduce the cold composition round-for-round."""
    from repro.serve import SchedulerPolicy
    eng = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                        respect_deps=True),
                        make_serving_device())
    eng.submit(_smoke_requests())
    cold = eng.composer.compose_dag(*(eng._work_items_dag()[:2]))
    warm = eng.composer.compose_dag(*(eng._work_items_dag()[:2]))
    assert eng.schedule_cache.dag_hits == 1
    assert [[t[0].name for t in rd] for rd in warm] == \
        [[t[0].name for t in rd] for rd in cold]


def test_replay_drift_triggers_revalidation():
    """A cached pattern whose stored modelled time drifts beyond
    ``replay_drift_tol`` from the replayed composition is rejected
    (counted as a revalidation) and the step recomposes cold; with
    the tolerance disabled the same replay is accepted optimistically."""
    from repro.serve import SchedulerPolicy
    eng = _smoke_engine(SchedulerPolicy(kind="symbiotic",
                                        respect_deps=True,
                                        replay_drift_tol=0.05),
                        make_serving_device())
    eng.submit(_smoke_requests())
    triples, traced = eng._work_items_dag()
    eng.composer.compose_dag(triples, traced)          # cold store
    key, _ = eng.composer.dag_key_and_labels(triples, traced)
    t0 = eng.schedule_cache.time_of(key)
    assert t0 is not None and t0 > 0
    # poison the stored time: the honest replay now "drifts" >5%
    eng.schedule_cache._times[key] = t0 * 2.0
    eng.composer.compose_dag(*(eng._work_items_dag()[:2]))
    assert eng.schedule_cache.replay_revalidations == 1
    # the cold recompose re-stored the honest time
    assert eng.schedule_cache.time_of(key) == pytest.approx(t0)
    # tol <= 0 replays the same poisoned entry optimistically
    eng.schedule_cache._times[key] = t0 * 2.0
    eng.policy.replay_drift_tol = 0.0
    eng.composer.compose_dag(*(eng._work_items_dag()[:2]))
    assert eng.schedule_cache.replay_revalidations == 1


# --------------------------------------------------------------------------
# slow sweep (ISSUE-4 CI satellite)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_sliced_dag_n512_sweep():
    """n=512 chain-structured DAG with oversized stages: sliced
    construction completes, stays topological, conserves node mass,
    and the gated makespan is no worse than unsliced."""
    rng = random.Random(37)
    items = _tpu_items(rng, 512, oversized_frac=0.1)
    profs = [it.profile() for it in items]
    edges = set()
    chains: list[list[int]] = [[] for _ in range(64)]
    for i in range(512):
        c = chains[rng.randrange(64)]
        if c:
            edges.add((c[-1], i))
        c.append(i)
    res = greedy_order_slices(profs, _TPU, edges=edges,
                              policy=SlicePolicy())
    g = res.graph()
    g.validate()
    assert g.is_topological(res.order)
    assert res.sliced
    un = greedy_order_dag(profs, _TPU, edges=edges)
    eids = KernelGraph(profs, edges).edges_by_id()
    t_un = DagEventSimulator(_TPU, eids).simulate(un.order)
    t_sl = DagEventSimulator(_TPU, res.edges_by_id()).simulate(res.order)
    assert t_sl <= t_un * (1 + 1e-9)


# --------------------------------------------------------------------------
# coalescing: same-round siblings merge back (inverse conservation law)
# --------------------------------------------------------------------------

def _work_mass(ks):
    """Total instructions and per-dimension demand mass over a node
    set (zero-work joins contribute nothing by construction)."""
    inst = sum(k.inst_per_block * k.n_blocks for k in ks)
    dims = {d for k in ks for d in k.demands}
    dem = {d: sum(k.demands.get(d, 0.0) * k.n_blocks for k in ks)
           for d in dims}
    return inst, dem


def test_merge_slice_profiles_full_merge_restores_parent():
    rng = random.Random(61)
    for prof in _gpu_kernels(rng, 6):
        sl = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=3), GTX580)
        parts = sl.slice_profile(prof, 3)
        merged = merge_slice_profiles(parts)
        assert merged.name == prof.name
        assert not is_slice(merged.name)
        i0, d0 = _work_mass([prof])
        i1, d1 = _work_mass([merged])
        assert i1 == pytest.approx(i0, rel=1e-12)
        for d in d0:
            assert d1[d] == pytest.approx(d0[d], rel=1e-12)


def test_merge_slice_profiles_partial_naming_roundtrip():
    rng = random.Random(62)
    prof = _gpu_kernels(rng, 1)[0]
    sl = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=4), GTX580)
    parts = sl.slice_profile(prof, 4)
    merged = merge_slice_profiles([parts[1], parts[3]])
    assert is_slice(merged.name)
    assert parent_name(merged.name) == prof.name
    ix, k = slice_indices(merged.name)
    assert (ix, k) == ([1, 3], 4)
    # a later pass can finish the merge: partial + remaining == parent
    done = merge_slice_profiles([merged, parts[0], parts[2]])
    assert done.name == prof.name
    assert done.n_blocks == prof.n_blocks


def test_merge_slice_profiles_mass_slices_conserve_totals():
    it = prefill_profile("r0:p:L0", n_params=7e9, seq_len=8192,
                         kv_bytes_per_token=131072)
    prof = it.profile()
    sl = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=2), _TPU)
    parts = sl.slice_profile(prof, 2)
    merged = merge_slice_profiles(parts)
    i0, d0 = _work_mass([prof])
    i1, d1 = _work_mass([merged])
    assert i1 == pytest.approx(i0, rel=1e-12)
    for d in d0:
        assert d1[d] == pytest.approx(d0[d], rel=1e-12)


def test_merge_slice_profiles_rejects_bad_groups():
    rng = random.Random(63)
    a, b = _gpu_kernels(rng, 2)
    sl = KernelSlicer(SlicePolicy(mode="fixed", fixed_k=2), GTX580)
    pa, pb = sl.slice_profile(a, 2), sl.slice_profile(b, 2)
    with pytest.raises(ValueError):
        merge_slice_profiles([pa[0], pb[1]])       # different parents
    with pytest.raises(ValueError):
        merge_slice_profiles([pa[0], pa[0]])       # duplicate index
    with pytest.raises(ValueError):
        merge_slice_profiles([])


def test_coalesce_rounds_conserves_and_keeps_makespan():
    """On a workload the round_fill policy over-slices, coalescing
    merges same-round siblings back: fewer nodes, identical work and
    demand mass, a still-topological order, and a bit-identical gated
    makespan (merged siblings ran side by side already)."""
    rng = random.Random(64)
    merged_any = False
    for trial in range(6):
        items = _tpu_items(rng, rng.randint(8, 16), oversized_frac=0.9)
        profs = [it.profile() for it in items]
        res = greedy_order_slices(
            profs, _TPU,
            policy=SlicePolicy(mode="round_fill", target_fill=0.2))
        out = coalesce_rounds(res)
        i0, d0 = _work_mass(res.kernels)
        i1, d1 = _work_mass(out.kernels)
        assert i1 == pytest.approx(i0, rel=1e-12)
        for d in d0:
            assert d1[d] == pytest.approx(d0.get(d, 0.0), rel=1e-12)
        g = out.graph()
        g.validate()
        assert g.is_topological(out.order)
        t0 = DagEventSimulator(_TPU, res.edges_by_id()).simulate(
            res.order)
        t1 = DagEventSimulator(_TPU, out.edges_by_id()).simulate(
            out.order)
        assert t1 == pytest.approx(t0, rel=1e-9)
        if len(out.kernels) < len(res.kernels):
            merged_any = True
            # every merge shrinks the graph; fully collapsed stages
            # must leave no orphan joins behind
            names = {k.name for k in out.kernels}
            for k in out.kernels:
                if is_join(k.name):
                    p = parent_name(k.name)
                    assert any(is_slice(nm) and not is_join(nm) and
                               parent_name(nm) == p for nm in names)
    assert merged_any


def test_coalesce_rounds_noop_when_siblings_spread():
    """When the composed schedule keeps siblings in distinct rounds
    (the common, useful case) coalescing is the identity."""
    rng = random.Random(65)
    items = _tpu_items(rng, 10, oversized_frac=0.35)
    profs = [it.profile() for it in items]
    res = greedy_order_slices(profs, _TPU, policy=SlicePolicy())
    out = coalesce_rounds(res)
    sibs_shared = any(
        len({parent_name(k.name) for k in rd.kernels
             if is_slice(k.name) and not is_join(k.name)}) <
        sum(1 for k in rd.kernels
            if is_slice(k.name) and not is_join(k.name))
        for rd in res.rounds)
    if not sibs_shared:
        assert out is res
