"""Property tests for the vectorized/incremental scheduling hot path:
the fast score matrix, the incremental greedy, delta-evaluated
refinement — each against the pure-Python reference as oracle — plus
the percentile-rank convention and the harmonic-ratio zero guard.

Written with plain ``random`` (no hypothesis dependency in the pinned
toolchain) over seeded draws, so failures reproduce exactly.
"""

import math
import random

import numpy as np
import pytest

from repro.core import (GTX580, DeviceModel, KernelProfile, RoundSimulator,
                        greedy_order, greedy_order_fast, percentile_rank,
                        score_matrix, score_matrix_fast, simulate)
from repro.core.refine import DeltaEvaluator, refine_order
from repro.core.resources import bs_kernel, ep_kernel, es_kernel, sw_kernel
from repro.core.scorer import combined_ratio, pair_score
from repro.core.tpu import (decode_profile, make_serving_device,
                            prefill_profile)

_FAMS = [ep_kernel, bs_kernel, es_kernel, sw_kernel]
_TPU = make_serving_device()


def _gpu_kernels(rng: random.Random, n: int) -> list[KernelProfile]:
    return [rng.choice(_FAMS)(f"k{i}",
                              grid=rng.choice([8, 16, 32, 48, 64, 96]),
                              shm=rng.choice([0, 4096, 8192, 16384, 24576]),
                              inst=rng.uniform(1e6, 5e8))
            for i in range(n)]


def _tpu_profiles(rng: random.Random, n: int) -> list[KernelProfile]:
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


def _round_names(sched) -> list[list[str]]:
    return [rd.names for rd in sched.rounds]


# --------------------------------------------------------------------------
# fast matrix == reference score_matrix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("device,maker", [(GTX580, _gpu_kernels),
                                          (_TPU, _tpu_profiles)])
def test_fast_matrix_matches_reference(device, maker):
    rng = random.Random(11)
    for _ in range(25):
        ks = maker(rng, rng.randint(2, 24))
        ref = np.asarray(score_matrix(ks, ks, device))
        fast = score_matrix_fast(ks, device)
        assert np.max(np.abs(ref - fast)) <= 1e-9


# --------------------------------------------------------------------------
# incremental greedy == reference greedy (exact round structure)
# --------------------------------------------------------------------------

def test_incremental_greedy_reproduces_reference():
    """>= 50 randomized kernel sets across both device families."""
    rng = random.Random(42)
    checked = 0
    for trial in range(60):
        if trial % 2 == 0:
            ks, dev = _gpu_kernels(rng, rng.randint(1, 20)), GTX580
        else:
            ks, dev = _tpu_profiles(rng, rng.randint(1, 32)), _TPU
        ref = _round_names(greedy_order(ks, dev))
        fast = _round_names(greedy_order_fast(ks, dev))
        assert ref == fast, f"trial {trial}: {ref} != {fast}"
        checked += 1
    assert checked >= 50


def test_incremental_greedy_matches_on_adversarial_dim_orders():
    """Equivalence must not depend on demands-dict order matching
    device.caps order, nor on the device having an "shm" dimension
    (exercises the solo-kernel sort-key fallback)."""
    rng = random.Random(77)
    dev = DeviceModel(name="odd", n_units=4,
                      caps={"a": 100.0, "b": 50.0}, max_resident=4,
                      compute_rate=1e9, mem_bw=1e9, r_balanced=2.0)
    for trial in range(30):
        ks = []
        for i in range(rng.randint(1, 12)):
            da = rng.uniform(1.0, 60.0)
            db = rng.uniform(1.0, 30.0)
            dem = {"b": db, "a": da} if rng.random() < 0.5 else \
                {"a": da, "b": db}
            ks.append(KernelProfile(f"k{i}", n_blocks=rng.randint(1, 16),
                                    demands=dem,
                                    inst_per_block=rng.uniform(1e5, 1e7),
                                    r=rng.uniform(0.5, 8.0)))
        ref = _round_names(greedy_order(ks, dev))
        fast = _round_names(greedy_order_fast(ks, dev))
        assert ref == fast, f"trial {trial}: {ref} != {fast}"
        ref_m = np.asarray(score_matrix(ks, ks, dev))
        assert np.max(np.abs(ref_m - score_matrix_fast(ks, dev))) <= 1e-9


def test_greedy_fast_empty_and_singleton():
    assert greedy_order_fast([], GTX580).rounds == []
    k = ep_kernel("only")
    sched = greedy_order_fast([k], GTX580)
    assert _round_names(sched) == [["only"]]


# --------------------------------------------------------------------------
# delta-evaluated refinement == full re-simulation (exact)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("device,maker", [(GTX580, _gpu_kernels),
                                          (_TPU, _tpu_profiles)])
def test_delta_eval_equals_full_resimulation(device, maker):
    rng = random.Random(5)
    sim = RoundSimulator(device)
    for _ in range(20):
        ks = maker(rng, rng.randint(2, 20))
        n = len(ks)
        ev = DeltaEvaluator(device, model="round")
        ev.rebase(ks)
        for _ in range(25):
            i, j = rng.randrange(n), rng.randrange(n)
            if i == j:
                continue
            cand = list(ks)
            cand[i], cand[j] = cand[j], cand[i]
            assert ev.evaluate(cand, min(i, j)) == sim.simulate(cand)
            cand = list(ks)
            cand.insert(j, cand.pop(i))
            assert ev.evaluate(cand, min(i, j)) == sim.simulate(cand)


def test_delta_refine_matches_reference_refine_small_n():
    """With the full move set the delta path retraces the reference
    trajectory exactly (same moves, same order, equal times)."""
    rng = random.Random(9)
    for _ in range(10):
        ks = _gpu_kernels(rng, rng.randint(3, 10))
        sim = RoundSimulator(GTX580)
        # budget high enough that both paths run to a local optimum
        # (the delta path's budget is charged fractionally, so at an
        # exhausted budget the two would stop at different points).
        o_ref, t_ref, _ = refine_order(
            ks, GTX580, time_fn=sim.simulate, budget=3000,
            neighborhood="full")
        o_fast, t_fast, _ = refine_order(
            ks, GTX580, model="round", budget=3000, neighborhood="full")
        assert t_fast == t_ref
        assert [k.name for k in o_fast] == [k.name for k in o_ref]


def test_refine_never_worse_than_input():
    rng = random.Random(3)
    for neighborhood in ("full", "adjacent", "auto"):
        ks = _gpu_kernels(rng, 12)
        t0 = RoundSimulator(GTX580).simulate(ks)
        _, t, _ = refine_order(ks, GTX580, model="round", budget=200,
                               neighborhood=neighborhood)
        assert t <= t0 + 1e-15


# --------------------------------------------------------------------------
# satellite pins
# --------------------------------------------------------------------------

def test_warm_start_insert_prefers_symbiotic_round():
    """The warm-start primitive places a joining decode step into the
    prefill round (compute/memory mixing, the paper's rule) and
    reports no-fit with -1."""
    from repro.core import warm_start_insert
    dev = make_serving_device()
    p = prefill_profile("p", n_params=7e9, seq_len=512,
                        kv_bytes_per_token=131072).profile()
    ds = [decode_profile(f"d{i}", n_params=7e9, kv_len=1024,
                         kv_bytes_per_token=131072).profile()
          for i in range(3)]
    idx = warm_start_insert([[p], [ds[0], ds[1]]], ds[2], dev)
    assert idx == 0
    # nothing fits: a round already at the token budget
    full = prefill_profile("big", n_params=7e9, seq_len=4096,
                           kv_bytes_per_token=131072).profile()
    assert warm_start_insert([[full]], ds[2], dev) == -1
    assert warm_start_insert([], ds[2], dev) == -1


def test_sat_dim_configs_match_reference():
    """_FastRoundSim._eff must mirror DeviceModel.*_efficiency under
    every sat_dim configuration — in caps, empty, and set-but-untracked
    (the audit fix: an untracked sat_dim carries no occupancy signal
    and must run at peak, not degrade to ~0 efficiency)."""
    rng = random.Random(19)
    base = dict(n_units=4, caps={"a": 100.0, "b": 50.0}, max_resident=4,
                compute_rate=1e9, mem_bw=1e9, r_balanced=2.0)
    devs = [DeviceModel(name="insat", sat_dim="a", sat_compute=30.0,
                        sat_memory=80.0, **base),
            DeviceModel(name="nosat", **base),
            DeviceModel(name="oddsat", sat_dim="zz", sat_compute=30.0,
                        sat_memory=80.0, **base)]
    for trial in range(10):
        ks = [KernelProfile(f"k{i}", n_blocks=rng.randint(1, 8),
                            demands={"a": rng.uniform(1, 40),
                                     "b": rng.uniform(1, 20)},
                            inst_per_block=rng.uniform(1e5, 1e7),
                            r=rng.uniform(0.5, 8.0))
              for i in range(rng.randint(2, 12))]
        for dev in devs:
            ref = RoundSimulator(dev).simulate(ks)
            ev = DeltaEvaluator(dev, model="round")
            assert ev.rebase(ks) == ref, (trial, dev.name)
            cand = list(ks)
            cand[0], cand[-1] = cand[-1], cand[0]
            assert ev.evaluate(cand, 0) == RoundSimulator(dev).simulate(
                cand), (trial, dev.name)
    # untracked sat_dim == no occupancy model: identical times
    ks = [KernelProfile("k", n_blocks=4, demands={"a": 10.0, "b": 5.0},
                        inst_per_block=1e6, r=2.0)]
    assert (RoundSimulator(devs[2]).simulate(ks)
            == RoundSimulator(devs[1]).simulate(ks))


def test_percentile_rank_convention():
    """percentile_rank returns a 0-100 percentage, not a fraction."""
    assert percentile_rank(1.0, [2.0, 1.5, 1.0, 0.5]) == 75.0
    assert percentile_rank(0.5, [2.0, 1.5, 1.0, 0.5]) == 100.0
    assert percentile_rank(3.0, [2.0, 1.5, 1.0, 0.5]) == 0.0
    assert percentile_rank(1.0, []) == 0.0


def test_harmonic_combined_ratio_zero_r_guard():
    """Pure-memory kernels (r == 0) must not divide by zero; the
    combined intensity degenerates to ~0 (memory-bound limit)."""
    a = KernelProfile("zero", n_blocks=4, demands={"shm": 0.0},
                      inst_per_block=1e6, r=0.0)
    b = KernelProfile("busy", n_blocks=4, demands={"shm": 0.0},
                      inst_per_block=1e6, r=10.0)
    rc = combined_ratio(a, b, mode="harmonic")
    assert math.isfinite(rc)
    assert rc == pytest.approx(0.0, abs=1e-12)
    # and the full scorer path survives it on a harmonic-mode device
    dev = make_serving_device()
    ka = KernelProfile("z", n_blocks=1,
                       demands={"vmem": 1.0, "hbm": 1.0, "slots": 1.0},
                       inst_per_block=1e6, r=0.0)
    kb = KernelProfile("c", n_blocks=1,
                       demands={"vmem": 1.0, "hbm": 1.0, "slots": 1.0},
                       inst_per_block=1e9, r=500.0)
    s = pair_score(ka, kb, dev)
    assert math.isfinite(s) and s >= 0.0
    fast = score_matrix_fast([ka, kb], dev)
    assert np.isfinite(fast).all()


def test_fast_path_end_to_end_quality_not_worse():
    """Fast greedy + delta refine produces modelled (event) times no
    worse than reference greedy + full-eval refine at equal budget."""
    rng = random.Random(21)
    for _ in range(5):
        ks = _gpu_kernels(rng, 10)
        ref_sched = greedy_order(ks, GTX580)
        sim = RoundSimulator(GTX580)
        o_ref, _, _ = refine_order(ref_sched.order, GTX580,
                                   time_fn=sim.simulate, budget=200)
        fast_sched = greedy_order_fast(ks, GTX580)
        o_fast, _, _ = refine_order(fast_sched.order, GTX580,
                                    model="round", budget=200,
                                    neighborhood="auto")
        t_ref = simulate(o_ref, GTX580)
        t_fast = simulate(o_fast, GTX580)
        assert t_fast <= t_ref + 1e-12


def test_refined_schedule_packs_profile_table_once(monkeypatch):
    """Greedy + refine packs the kernel set into a ProfileTable once:
    the delta-evaluated refiner resolves its own per-kernel tuples."""
    from repro.core import refined_schedule
    from repro.core.fastscore import ProfileTable

    rng = random.Random(24)
    ks = _gpu_kernels(rng, 24)
    builds = []
    real_build = ProfileTable.build.__func__

    def counting_build(cls, kernels, device):
        builds.append(len(kernels))
        return real_build(cls, kernels, device)

    monkeypatch.setattr(ProfileTable, "build",
                        classmethod(counting_build))
    refined_schedule(ks, GTX580, budget=20, neighborhood="adjacent")
    assert builds == [len(ks)]
