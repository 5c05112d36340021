"""Unit tests for the serving ScheduleCache (no model, no jax device
work): signatures, key multisets, namespaced keys, pattern replay,
LRU bound and refresh accounting, near-miss warm starts."""

import pytest

from repro.serve import ScheduleCache


def test_signature_buckets_decode_kv_lens():
    c = ScheduleCache(kv_bucket=256)
    assert c.signature("decode", 0) == c.signature("decode", 255)
    assert c.signature("decode", 255) != c.signature("decode", 256)
    # prefill is keyed by exact token count (compiled geometry)
    assert c.signature("prefill", 128) != c.signature("prefill", 129)


def test_key_is_order_invariant_multiset():
    a = [("d", 1), ("p", 128), ("d", 1)]
    b = [("d", 1), ("d", 1), ("p", 128)]
    assert ScheduleCache.key_of(a) == ScheduleCache.key_of(b)
    assert ScheduleCache.key_of(a) != ScheduleCache.key_of(a[:2])


def test_lookup_store_and_hit_accounting():
    c = ScheduleCache()
    key = ("flat", "symbiotic",
           ScheduleCache.key_of([("d", 0), ("p", 8)]))
    assert c.lookup(key) is None
    pattern = ((("p", 8), ("d", 0)),)
    c.store(key, pattern)
    assert c.lookup(key) == pattern
    assert c.hits == 1 and c.misses == 1
    assert c.hit_rate == 0.5
    assert c.stats()["entries"] == 1


def test_lru_eviction_bound():
    c = ScheduleCache(max_entries=4)
    for i in range(10):
        c.store(("flat", "k", i), ())
    assert len(c._store) == 4
    assert (("flat", "k", 9) in c._store and
            ("flat", "k", 5) not in c._store)


def test_restore_refreshes_lru_position():
    """Re-storing an existing key must move it to the fresh end:
    without move_to_end a refreshed entry kept its stale position and
    was evicted as if it were never touched."""
    c = ScheduleCache(max_entries=3)
    c.store(("flat", "k", 1), ())
    c.store(("flat", "k", 2), ())
    c.store(("flat", "k", 3), ())
    c.store(("flat", "k", 1), ((("d", 0),),))  # refresh oldest entry
    c.store(("flat", "k", 4), ())   # evicts the true LRU: ("k", 2)
    assert ("flat", "k", 1) in c._store
    assert ("flat", "k", 2) not in c._store
    assert c._store[("flat", "k", 1)] == ((("d", 0),),)


def _key(kind, sigs):
    return ("flat", kind, ScheduleCache.key_of(list(sigs)))


def test_near_miss_one_joined():
    c = ScheduleCache()
    pat = ((("p", 8), ("d", 0)), (("d", 0),))
    c.store(_key("symbiotic", [("p", 8), ("d", 0), ("d", 0)]), pat)
    # one decode joined the mix
    got = c.near_miss(_key("symbiotic",
                           [("p", 8), ("d", 0), ("d", 0), ("d", 1)]))
    assert got is not None
    pattern, added, removed = got
    assert pattern == pat and added == [("d", 1)] and removed == []


def test_near_miss_one_left():
    c = ScheduleCache()
    pat = ((("p", 8), ("d", 0)), (("d", 0),))
    c.store(_key("symbiotic", [("p", 8), ("d", 0), ("d", 0)]), pat)
    got = c.near_miss(_key("symbiotic", [("p", 8), ("d", 0)]))
    assert got is not None
    pattern, added, removed = got
    assert pattern == pat and added == [] and removed == [("d", 0)]


def test_near_miss_rejects_far_keys_and_other_kinds():
    c = ScheduleCache()
    c.store(_key("symbiotic", [("d", 0), ("d", 0)]), ())
    # two signatures differ (a substitution): not a near miss
    assert c.near_miss(_key("symbiotic", [("d", 1), ("d", 2)])) is None
    # same multiset distance but different policy kind
    assert c.near_miss(_key("refined", [("d", 0)])) is None
    # identical key is a lookup hit, not a near miss
    assert c.near_miss(_key("symbiotic", [("d", 0), ("d", 0)])) is None


def test_warm_hits_surface_in_stats():
    c = ScheduleCache()
    assert c.stats()["warm_hits"] == 0
    c.warm_hits += 1
    assert c.stats()["warm_hits"] == 1


def test_warm_regret_accounting():
    """Warm-start quality audit (ROADMAP item): sampled warm hits
    record their modelled regret; the mean surfaces in stats() and is
    0.0 with no samples (not NaN)."""
    c = ScheduleCache()
    s = c.stats()
    assert s["warm_sampled"] == 0 and s["warm_regret_mean"] == 0.0
    c.record_warm_regret(0.10)
    c.record_warm_regret(-0.02)
    s = c.stats()
    assert s["warm_sampled"] == 2
    assert abs(s["warm_regret_mean"] - 0.04) < 1e-12


def test_store_records_model_time_for_drift_checks():
    """Stale-replay re-validation (ROADMAP item) compares the replayed
    composition's modelled time against the one recorded at store
    time; patterns stored without a time opt out (None)."""
    c = ScheduleCache()
    c.store(("flat", "k", 1), (), 0.125)
    c.store(("flat", "k", 2), ())
    assert c.time_of(("flat", "k", 1)) == 0.125
    assert c.time_of(("flat", "k", 2)) is None
    assert c.time_of(("flat", "k", 3)) is None  # never stored
    # eviction drops the recorded time alongside the pattern
    small = ScheduleCache(max_entries=2)
    small.store(("flat", "k", 1), (), 1.0)
    small.store(("flat", "k", 2), (), 2.0)
    small.store(("flat", "k", 3), (), 3.0)
    assert small.time_of(("flat", "k", 1)) is None
    assert small.time_of(("flat", "k", 3)) == 3.0


def test_new_counters_surface_in_stats():
    c = ScheduleCache()
    s = c.stats()
    assert s["dag_hits"] == 0 and s["replay_revalidations"] == 0
    c.dag_hits += 2
    c.replay_revalidations += 1
    s = c.stats()
    assert s["dag_hits"] == 2 and s["replay_revalidations"] == 1


def test_warm_audit_sampling_is_deterministic():
    """The engine samples warm hits when the counter crosses integer
    multiples of 1/frac — verify the crossing rule the engine uses."""
    def sampled(seen, frac):
        return int(seen * frac) > int((seen - 1) * frac)
    assert [s for s in range(1, 9) if sampled(s, 0.25)] == [4, 8]
    assert [s for s in range(1, 5) if sampled(s, 1.0)] == [1, 2, 3, 4]
    assert [s for s in range(1, 9) if sampled(s, 0.0)] == []


def test_keys_are_namespaced():
    """PR 7: every key names its path — flat or dag — so a traced step
    can never consult a flat-signature pattern (the PR 3 cache-bypass
    wart, now structurally impossible)."""
    import pytest

    c = ScheduleCache()
    with pytest.raises(AssertionError):
        c.store(("symbiotic", (("d", 0),)), ())     # legacy 2-tuple
    with pytest.raises(AssertionError):
        c.lookup(("symbiotic", (("d", 0),)))
    key = ("flat", "symbiotic", (("d", 0),))
    c.store(key, ())
    assert c.lookup(key, namespace="flat") == ()
    with pytest.raises(AssertionError):
        c.lookup(key, namespace="dag")              # wrong path
    dkey = ("dag", "symbiotic", ((("d", 0), 3),))
    c.store(dkey, ())
    assert c.lookup(dkey, namespace="dag") == ()
    with pytest.raises(AssertionError):
        c.near_miss(dkey)       # warm adaptation is flat-only


def test_near_miss_never_crosses_namespaces():
    c = ScheduleCache()
    # a dag entry whose (kind, len±1) shape would match the flat scan
    c.store(("dag", "symbiotic", (("d", 0),)), ())
    assert c.near_miss(("flat", "symbiotic",
                        (("d", 0), ("d", 0)))) is None


def test_incremental_counters_surface_in_stats():
    c = ScheduleCache()
    s = c.stats()
    assert s["incremental_joins"] == 0
    assert s["incremental_leaves"] == 0
    assert s["frontier_rebuilds"] == 0
    assert s["gated_sims_saved"] == 0.0
    c.incremental_joins += 3
    c.incremental_leaves += 2
    c.frontier_rebuilds += 1
    c.gated_sims_saved += 0.75
    s = c.stats()
    assert s["incremental_joins"] == 3
    assert s["incremental_leaves"] == 2
    assert s["frontier_rebuilds"] == 1
    assert abs(s["gated_sims_saved"] - 0.75) < 1e-12


# --------------------------------------------------------------------------
# the default flat composer: every outcome compose() can serve
# --------------------------------------------------------------------------

_N_PARAMS = 4.6e8            # qwen1.5-0.5b-sized weight stream
_KV_BYTES = 98304.0          # its bf16 KV bytes per token


def _flat_items(spec):
    """Work-item triples for ``spec``: (rid, "p"|"d", prompt or kv len)."""
    import numpy as np

    from repro.core.tpu import decode_profile, prefill_profile
    from repro.serve import Request

    items = []
    for rid, kind, n in spec:
        r = Request(rid, np.zeros(n, np.int32))
        if kind == "p":
            it = prefill_profile(f"prefill:{rid}", n_params=_N_PARAMS,
                                 seq_len=n, kv_bytes_per_token=_KV_BYTES)
            items.append((it, r, "prefill"))
        else:
            r.cache, r.pos = object(), n
            it = decode_profile(f"decode:{rid}", n_params=_N_PARAMS,
                                kv_len=n, kv_bytes_per_token=_KV_BYTES)
            items.append((it, r, "decode"))
    return items


_MIX = [(0, "p", 128), (1, "d", 1), (2, "d", 64), (3, "d", 1024),
        (4, "d", 2048), (5, "p", 4)]

#: outcome -> (steps composed in order, expected ``served`` of the last)
_FLAT_OUTCOMES = {
    "cold": ([_MIX], "cold"),
    "fifo_guard": ([[(0, "p", 128), (1, "p", 4000), (2, "d", 4000)]],
                   "fifo"),
    "replay": ([_MIX, _MIX], "replay"),
    "warm_join": ([_MIX, _MIX + [(6, "d", 300)]], "warm"),
    "warm_leave": ([_MIX, _MIX[:-1]], "warm"),
    # same signatures (kv lens stay in one bucket), but the decodes'
    # KV reads grew past the drift tolerance: the replay is rejected
    "stale_replay": ([[(i, "d", 1) for i in range(8)],
                      [(i, "d", 4000) for i in range(8)]], "cold"),
}


@pytest.mark.parametrize("outcome", sorted(_FLAT_OUTCOMES))
def test_default_flat_composer_outcomes(outcome):
    from repro.core.tpu import fifo_rounds, make_serving_device, round_time
    from repro.obs import FlightRecorder
    from repro.serve import SchedulerPolicy
    from repro.serve.composer import Composer

    steps, served = _FLAT_OUTCOMES[outcome]
    dev, wb = make_serving_device(), 2.0 * _N_PARAMS
    rec = FlightRecorder()
    # one decode signature up to 4K of KV, so the stale case's
    # growing KV reads still hit the cached pattern
    comp = Composer(SchedulerPolicy(), dev, wb,
                    ScheduleCache(kv_bucket=4096), recorder=rec)
    for spec in steps:
        items = _flat_items(spec)
        rounds = comp.compose(items)
    notes = [e for e in rec.events if e["kind"] == "schedule"]
    assert len(notes) == len(steps)
    assert notes[-1]["served"] == served
    if outcome == "stale_replay":
        assert [e["outcome"] for e in rec.events
                if e["kind"] == "cache"] == ["revalidated"]
    # every live item exactly once
    served_ids = sorted(id(t) for rd in rounds for t in rd)
    assert served_ids == sorted(id(t) for t in items)
    # never modelled-worse than arrival-order packing
    t = sum(round_time([x[0] for x in rd], dev, wb) for rd in rounds)
    t_fifo = sum(round_time(rd, dev, wb)
                 for rd in fifo_rounds([x[0] for x in items], dev))
    assert t <= t_fifo * (1 + 1e-12)
