"""Property tests for the batched candidate-evaluation path
(:mod:`repro.core.batched`) and the admission/completion scan kernel
(:mod:`repro.kernels.event_scan`).

The contracts pinned here are the ones the refiner relies on:

* the batched round engine is **bit-exact** against ``_FastRoundSim``
  (fresh starts and checkpoint-stitched resumes alike);
* the batched event/gated engines agree with the sequential delta
  evaluators within ``EVENT_TIME_RTOL`` (pure summation-order noise);
* the f32 scan kernel (``jit(vmap)`` and Pallas interpret dispatch)
  agrees with ``_FastEventSim`` within ``F32_EVENT_RTOL``, including
  the degenerate oversized-block drain path;
* ``batch_size=`` routing through :func:`repro.core.refine.refine_order`
  / :func:`repro.graph.refine_order_dag` returns legal permutations
  never modelled-worse than their input, and the greedy + refine
  pipeline packs its :class:`ProfileTable` exactly once.

Written with plain ``random`` (no hypothesis dependency in the pinned
toolchain) over seeded draws, so failures reproduce exactly.
"""

import random

import numpy as np
import pytest

from repro.core import GTX580, KernelProfile
from repro.core.batched import (EVENT_TIME_RTOL, HAS_JAX, BatchedEventSim,
                                BatchedRoundSim, PackedKernels,
                                audit_pair_scores, pair_score_matrix_batched,
                                refine_order_batched)
from repro.core.fastscore import ProfileTable, greedy_order_fast
from repro.core.refine import (DeltaEvaluator, _apply, _FastEventSim,
                               _FastRoundSim, _moves, refine_order,
                               refined_schedule)
from repro.core.resources import bs_kernel, ep_kernel, es_kernel, sw_kernel
from repro.graph.constrained import refine_order_dag
from repro.graph.delta import GatedDeltaEvaluator
from repro.kernels import event_scan

_FAMS = [ep_kernel, bs_kernel, es_kernel, sw_kernel]


def _gpu_kernels(rng: random.Random, n: int) -> list[KernelProfile]:
    return [rng.choice(_FAMS)(f"k{i}",
                              grid=rng.choice([8, 16, 32, 48, 64, 96]),
                              shm=rng.choice([0, 4096, 8192, 16384, 24576]),
                              inst=rng.uniform(1e6, 5e8))
            for i in range(n)]


def _oversized(rng: random.Random, n: int) -> list[KernelProfile]:
    """Profiles whose blocks exceed device capacity in some dimension,
    forcing the simulator's degenerate solo-drain path — the branch the
    scan kernel implements as ``passes * t1``."""
    ks = []
    for i in range(n):
        if rng.random() < 0.5:
            dem = {"shm": rng.choice([49152.0, 96000.0]),
                   "reg": rng.uniform(100, 3000.0), "warp": 4.0}
        else:
            dem = {"shm": rng.choice([0.0, 8192.0]),
                   "reg": rng.uniform(512, 8192.0),
                   "warp": float(rng.choice([1, 4, 8, 16]))}
        ks.append(KernelProfile(
            f"a{i}", n_blocks=rng.choice([1, 3, 7, 17, 33]),
            demands=dem, inst_per_block=rng.uniform(1e2, 1e9),
            r=rng.choice([1e-6, 0.5, 4.0, 1e6])))
    return ks


def _chain_edges(rng: random.Random, n: int,
                 width: int) -> set[tuple[int, int]]:
    """Layered DAG over indices 0..n-1 (index order is topological):
    each node depends on 1-2 nodes from the previous layer."""
    edges: set[tuple[int, int]] = set()
    for v in range(width, n):
        layer_lo = max(0, v - 2 * width)
        for _ in range(rng.choice([1, 2])):
            u = rng.randrange(layer_lo, v)
            edges.add((u, v))
    return edges


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------------------
# batched engines vs sequential references — fresh starts
# ---------------------------------------------------------------------------

def test_batched_round_fresh_is_bit_exact():
    for trial in range(8):
        rng = random.Random(100 + trial)
        ks = _gpu_kernels(rng, rng.choice([8, 16, 24, 40]))
        pk = PackedKernels.for_table(ProfileTable.build(ks, GTX580))
        orders = []
        for b in range(5):
            o = list(ks)
            random.Random(trial * 10 + b).shuffle(o)
            orders.append(o)
        rows = np.stack([pk.rows(o) for o in orders])
        tb = BatchedRoundSim(pk).times_from_checkpoints(
            rows, [None] * len(orders))
        sim = _FastRoundSim(GTX580)
        for b, o in enumerate(orders):
            assert tb[b] == sim.simulate(o)[0]


def test_batched_event_fresh_within_rtol():
    for trial in range(8):
        rng = random.Random(200 + trial)
        ks = _gpu_kernels(rng, rng.choice([8, 16, 24, 40]))
        pk = PackedKernels.for_table(ProfileTable.build(ks, GTX580))
        orders = []
        for b in range(5):
            o = list(ks)
            random.Random(trial * 10 + b).shuffle(o)
            orders.append(o)
        rows = np.stack([pk.rows(o) for o in orders])
        tb = BatchedEventSim(pk).times(rows, [None] * len(orders))
        sim = _FastEventSim(GTX580)
        for b, o in enumerate(orders):
            assert _rel(tb[b], sim.simulate(o)[0]) <= EVENT_TIME_RTOL


def test_batched_event_oversized_blocks_fresh():
    for trial in range(4):
        rng = random.Random(300 + trial)
        ks = _oversized(rng, 16)
        pk = PackedKernels.for_table(ProfileTable.build(ks, GTX580))
        orders = []
        for b in range(4):
            o = list(ks)
            random.Random(trial * 10 + b).shuffle(o)
            orders.append(o)
        rows = np.stack([pk.rows(o) for o in orders])
        tb = BatchedEventSim(pk).times(rows, [None] * len(orders))
        sim = _FastEventSim(GTX580)
        for b, o in enumerate(orders):
            assert _rel(tb[b], sim.simulate(o)[0]) <= EVENT_TIME_RTOL


# ---------------------------------------------------------------------------
# batched engines vs the union of sequential delta evaluations —
# checkpoint-stitched resumes (the refiner's actual workload)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["round", "event", "gated"])
def test_batched_resume_equals_sequential_delta(model):
    for trial in range(5):
        rng = random.Random(400 + trial)
        n = rng.choice([16, 24, 32])
        ks = _gpu_kernels(rng, n)
        edge_ids = None
        if model == "gated":
            edges = _chain_edges(rng, n, width=max(4, n // 8))
            edge_ids = {(id(ks[u]), id(ks[v])) for u, v in edges}
            delta = GatedDeltaEvaluator(GTX580, edge_ids)
            base = list(ks)  # index order is topological
        else:
            delta = DeltaEvaluator(GTX580, model=model)
            base = list(ks)
            random.Random(trial).shuffle(base)
        delta.rebase(base)
        pk = PackedKernels.for_table(ProfileTable.build(ks, GTX580))
        if model == "round":
            bsim = BatchedRoundSim(pk)
        else:
            bsim = BatchedEventSim(pk, edge_ids)
        cands, firsts = [], []
        for first, kind, i, j in _moves(n, "adjacent")[:20]:
            cand = _apply(base, kind, i, j)
            if model == "gated" and not delta.legal(cand):
                continue
            cands.append(cand)
            firsts.append(first)
        assert cands, "neighborhood produced no (legal) candidates"
        rows = np.stack([pk.rows(c) for c in cands])
        cps = []
        for first in firsts:
            if model == "round":
                cp = None
                for c in delta._ckpts:
                    if c.pos < first and (cp is None or c.pos > cp.pos):
                        cp = c
                cps.append(cp)
            else:
                cps.append(delta._ckpts[first])
        if model == "round":
            tb = bsim.times_from_checkpoints(rows, cps)
        else:
            tb = bsim.times(rows, cps)
        for b, cand in enumerate(cands):
            tr, _ = delta.evaluate_costed(cand, firsts[b])
            if model == "round":
                assert tb[b] == tr
            else:
                assert _rel(tb[b], tr) <= EVENT_TIME_RTOL


# ---------------------------------------------------------------------------
# f32 pair-score matrix
# ---------------------------------------------------------------------------

def test_audit_pair_scores_numpy_backend():
    rng = random.Random(11)
    table = ProfileTable.build(_gpu_kernels(rng, 48), GTX580)
    audit = audit_pair_scores(table, backend="numpy")
    assert audit["within_tol"], audit


@pytest.mark.skipif(not HAS_JAX, reason="jax unavailable")
def test_audit_pair_scores_jax_backend():
    rng = random.Random(12)
    table = ProfileTable.build(_gpu_kernels(rng, 48), GTX580)
    audit = audit_pair_scores(table, backend="jax")
    assert audit["within_tol"], audit
    # both f32 backends run the same arithmetic — they agree far more
    # tightly with each other than either does with the f64 reference
    a = pair_score_matrix_batched(table, backend="numpy")
    b = pair_score_matrix_batched(table, backend="jax")
    scale = max(float(np.max(np.abs(a))), 1.0)
    assert float(np.max(np.abs(a - b))) <= 1e-6 * scale


# ---------------------------------------------------------------------------
# the admission/completion scan kernel (repro.kernels.event_scan)
# ---------------------------------------------------------------------------

def _scan_rows(rng: random.Random, table, B: int) -> np.ndarray:
    n = len(table.kernels)
    rows = []
    for _ in range(B):
        perm = list(range(n))
        rng.shuffle(perm)
        rows.append(perm)
    return np.asarray(rows, dtype=np.int32)


@pytest.mark.skipif(not event_scan.HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("dispatch", ["jax", "pallas"])
def test_event_scan_matches_fast_event_sim(dispatch):
    for trial in range(4):
        rng = random.Random(500 + trial)
        table = ProfileTable.build(
            _gpu_kernels(rng, rng.choice([8, 16, 24])), GTX580)
        rows = _scan_rows(rng, table, B=6)
        if dispatch == "jax":
            got = event_scan.event_times_jax(rows, table)
        else:
            got = event_scan.event_times_pallas(rows, table,
                                                interpret=True)
        ref = event_scan.event_times_reference(rows, table)
        for b in range(rows.shape[0]):
            assert _rel(float(got[b]), float(ref[b])) \
                <= event_scan.F32_EVENT_RTOL


@pytest.mark.skipif(not event_scan.HAS_JAX, reason="jax unavailable")
@pytest.mark.parametrize("dispatch", ["jax", "pallas"])
def test_event_scan_oversized_blocks(dispatch):
    """Adversarial profiles: per-block demands above device caps drive
    the scan through its ``passes * t1`` solo-drain branch."""
    for trial in range(3):
        rng = random.Random(600 + trial)
        table = ProfileTable.build(_oversized(rng, 12), GTX580)
        rows = _scan_rows(rng, table, B=4)
        if dispatch == "jax":
            got = event_scan.event_times_jax(rows, table)
        else:
            got = event_scan.event_times_pallas(rows, table,
                                                interpret=True)
        ref = event_scan.event_times_reference(rows, table)
        for b in range(rows.shape[0]):
            assert _rel(float(got[b]), float(ref[b])) \
                <= event_scan.F32_EVENT_RTOL


@pytest.fixture
def accelerator():
    """Skip unless JAX runs on a TPU or GPU.  Asked when a test that
    needs it starts, never at collection: on a machine with a chip,
    every collecting worker would otherwise load its runtime."""
    import jax
    if jax.default_backend() not in ("tpu", "gpu"):
        pytest.skip("no TPU/GPU jax backend: compiled Pallas path "
                    "unavailable (interpret-mode tests cover the logic)")


def test_event_scan_compiled_pallas(accelerator):
    """The compiled (non-interpret) Pallas dispatch — only meaningful
    on a real accelerator backend."""
    rng = random.Random(7)
    table = ProfileTable.build(_gpu_kernels(rng, 16), GTX580)
    rows = _scan_rows(rng, table, B=4)
    got = event_scan.event_times_pallas(rows, table, interpret=False)
    ref = event_scan.event_times_reference(rows, table)
    for b in range(rows.shape[0]):
        assert _rel(float(got[b]), float(ref[b])) \
            <= event_scan.F32_EVENT_RTOL


# ---------------------------------------------------------------------------
# batch_size routing through the public refiners
# ---------------------------------------------------------------------------

def test_refine_order_batched_never_worse_and_permutation():
    for model in ("round", "event"):
        rng = random.Random(21)
        ks = _gpu_kernels(rng, 32)
        base = greedy_order_fast(ks, GTX580).order
        t0 = DeltaEvaluator(GTX580, model=model).rebase(base)
        out, t, evals = refine_order(base, GTX580, model=model,
                                     budget=40, neighborhood="adjacent",
                                     batch_size=16)
        assert t <= t0 + 1e-12
        assert sorted(id(k) for k in out) == sorted(id(k) for k in base)
        assert evals >= 1


def test_refine_order_batched_matches_currency():
    """The returned time is the *sequential* simulator's own currency
    for the returned order (acceptances are exactly re-verified)."""
    rng = random.Random(22)
    ks = _gpu_kernels(rng, 24)
    base = greedy_order_fast(ks, GTX580).order
    out, t, _ = refine_order(base, GTX580, model="event", budget=40,
                             neighborhood="adjacent", batch_size=16)
    assert _FastEventSim(GTX580).simulate(out)[0] == pytest.approx(
        t, rel=1e-12)


def test_refine_order_dag_batched_gated_legal_and_no_worse():
    rng = random.Random(23)
    n = 24
    ks = _gpu_kernels(rng, n)
    edges = _chain_edges(rng, n, width=max(4, n // 8))
    edge_ids = {(id(ks[u]), id(ks[v])) for u, v in edges}
    base = list(ks)  # topological by construction
    t0 = GatedDeltaEvaluator(GTX580, edge_ids).rebase(base)
    out, t, _ = refine_order_dag(base, GTX580, edge_ids=edge_ids,
                                 model="gated", budget=30,
                                 neighborhood="adjacent", batch_size=16)
    assert t <= t0 + 1e-12
    pos = {id(k): i for i, k in enumerate(out)}
    for u, v in edge_ids:
        assert pos[u] < pos[v]


def test_batched_gated_parity_with_sequential_refiner():
    """The ISSUE-6 quality pin: under the default gated contract
    (``rescore`` on), the batched walk re-scores the chunk remainder
    after every acceptance and therefore retraces the sequential
    first-improving sweep wherever the engine classifies
    improving/non-improving correctly — refined makespans match the
    *sequential refiner's*, not just the input order's."""
    from repro.core.tpu import (decode_profile, make_serving_device,
                                prefill_profile)
    from repro.graph.constrained import greedy_order_dag

    dev = make_serving_device(n_units=4)
    exact = 0
    for seed in range(6):
        rng = random.Random(seed)
        n = 40
        ks = []
        for i in range(n):
            if rng.random() < 0.3:
                it = prefill_profile(
                    f"p{i}", n_params=7e9,
                    seq_len=rng.choice([128, 256, 512, 1024]),
                    kv_bytes_per_token=131072)
            else:
                it = decode_profile(
                    f"d{i}", n_params=7e9,
                    kv_len=rng.randint(64, 8192),
                    kv_bytes_per_token=131072)
            ks.append(it.profile())
        edges: set[tuple[int, int]] = set()
        chains: list[list[int]] = [[] for _ in range(6)]
        for i in range(n):
            c = chains[rng.randrange(6)]
            if c:
                edges.add((c[-1], i))
            c.append(i)
        eids = {(id(ks[u]), id(ks[v])) for u, v in edges}
        order = greedy_order_dag(ks, dev, edges=edges).order
        _, t_seq, _ = refine_order_dag(
            order, dev, edge_ids=eids, model="gated", budget=10,
            neighborhood="adjacent")
        _, t_bat, _ = refine_order_dag(
            order, dev, edge_ids=eids, model="gated", budget=10,
            neighborhood="adjacent", batch_size=32)
        assert t_bat <= t_seq * (1 + 1e-9)
        exact += t_bat == t_seq
        # the fast contract (rescore off) only pins to the input:
        t0 = GatedDeltaEvaluator(dev, eids).rebase(list(order))
        _, t_fast, _ = refine_order_dag(
            order, dev, edge_ids=eids, model="gated", budget=10,
            neighborhood="adjacent", batch_size=32, rescore=False)
        assert t_fast <= t0 + 1e-12
    # most trajectories retrace the sequential one bit-for-bit
    assert exact >= 3


def test_refined_schedule_packs_profile_table_once(monkeypatch):
    rng = random.Random(24)
    ks = _gpu_kernels(rng, 24)
    builds = []
    real_build = ProfileTable.build.__func__

    def counting_build(cls, kernels, device):
        builds.append(len(kernels))
        return real_build(cls, kernels, device)

    monkeypatch.setattr(ProfileTable, "build",
                        classmethod(counting_build))
    refined_schedule(ks, GTX580, budget=20, neighborhood="adjacent",
                     batch_size=16)
    assert builds == [len(ks)]


def test_refine_order_batch_size_rejected_with_custom_time_fn():
    rng = random.Random(25)
    ks = _gpu_kernels(rng, 8)
    # custom time_fn has no batched counterpart: routing must not
    # engage (documented contract — falls back to sequential).
    out, t, _ = refine_order(ks, GTX580,
                             time_fn=lambda o: float(len(o)),
                             budget=5, batch_size=8)
    assert t == float(len(ks))


# ---------------------------------------------------------------------------
# slow sweep
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_batched_event_n1024_sweep():
    rng = random.Random(31)
    ks = _gpu_kernels(rng, 1024)
    pk = PackedKernels.for_table(ProfileTable.build(ks, GTX580))
    orders = []
    for b in range(3):
        o = list(ks)
        random.Random(b).shuffle(o)
        orders.append(o)
    rows = np.stack([pk.rows(o) for o in orders])
    tb = BatchedEventSim(pk).times(rows, [None] * len(orders))
    sim = _FastEventSim(GTX580)
    for b, o in enumerate(orders):
        assert _rel(tb[b], sim.simulate(o)[0]) <= EVENT_TIME_RTOL
