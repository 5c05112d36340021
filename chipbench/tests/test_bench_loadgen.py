"""The traffic generator: deterministic by seed, the same work for
every seed."""

import json

import numpy as np
import pytest

from chipbench import loadgen, spec


def _mix(name):
    with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _key(reqs):
    return [(r.rid, r.due, r.prompt.tolist(), r.max_new_tokens)
            for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 40 + 3])
def test_open_loop_same_seed_same_inputs(seed):
    mix = _mix("short-chat-open")
    a = loadgen.open_loop(mix, seed, 50.0, 151936)
    b = loadgen.open_loop(mix, seed, 50.0, 151936)
    assert _key(a) == _key(b)
    assert all(0 <= r.due < 50.0 for r in a)
    assert all(0 <= t < 151936 for r in a for t in r.prompt)


def test_open_loop_seeds_offer_the_same_work():
    """Every seed sends the same sizes at the same times; only the
    token ids differ. The mix's order seed sets the order."""
    mix = _mix("short-chat-open")
    runs = [loadgen.open_loop(mix, s, 50.0, 1000) for s in (1, 2, 3)]
    shape = [[(r.due, len(r.prompt), r.max_new_tokens) for r in reqs]
             for reqs in runs]
    assert shape[0] == shape[1] == shape[2]
    assert _key(runs[0]) != _key(runs[1])
    assert abs(len(runs[0]) - mix["rate_per_s"] * 50) <= mix["block"]
    other = loadgen.open_loop(dict(mix, order_seed=2), 1, 50.0, 1000)
    assert [(r.due, len(r.prompt)) for r in other] != \
        [(d, p) for d, p, _ in shape[0]]
    n = int(np.ceil(mix["rate_per_s"] * 50 / mix["block"])) * mix["block"]
    full = loadgen.quantiles(mix["prompt_len"], n)
    assert set(len(r.prompt) for r in runs[0]) <= set(full.tolist())
    assert full.min() >= mix["prompt_len"]["min"]
    assert full.max() <= mix["prompt_len"]["max"]


def test_stratified_order_keeps_one_value_of_each_stratum_per_block():
    rng = np.random.default_rng(5)
    vals = np.arange(48)
    out = loadgen.stratified_order(vals, 8, rng)
    assert sorted(out.tolist()) == vals.tolist()
    for b in range(6):
        blk = out[b * 8:(b + 1) * 8]
        assert sorted(v // 6 for v in blk) == list(range(8))


def test_closed_loop_epochs_keep_their_totals():
    mix = _mix("long-prompt-closed")
    n = mix["requests_per_epoch"]
    sums = set()
    for seed in (3, 4):
        src = loadgen.ClosedLoopSource(mix, seed, 32000)
        for _ in range(2):
            epoch = src.take(n)
            sums.add((sum(len(r.prompt) for r in epoch),
                      sum(r.max_new_tokens for r in epoch)))
            for b in range(n // mix["block"]):
                blk = epoch[b * mix["block"]:(b + 1) * mix["block"]]
                assert len(blk) == mix["block"]
    assert len(sums) == 1
    a = loadgen.ClosedLoopSource(mix, 3, 32000).take(40)
    b = loadgen.ClosedLoopSource(mix, 3, 32000).take(40)
    c = loadgen.ClosedLoopSource(mix, 4, 32000).take(40)
    assert _key(a) == _key(b) != _key(c)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert [r.rid for r in a] == list(range(40))


@pytest.mark.parametrize("name", ["short-chat-open", "long-prompt-closed",
                                  "short-prompt-long-output-closed"])
def test_mix_keeps_its_source_statistics(name):
    """The stratified set keeps the statistic the mix's source publishes
    (a mean or a median) within 2%, and every cut the mix lists under
    ``reduced`` is the value it runs."""
    mix = _mix(name)
    assert mix["source"]
    for key in ("prompt_len", "output_len"):
        d = mix[key]
        v = loadgen.quantiles(d, 128)
        if "mean" in d:
            assert v.mean() == pytest.approx(d["mean"], rel=0.02)
        else:
            assert np.median(v) == pytest.approx(d["median"], rel=0.02)
    for path, cut in mix["reduced"].items():
        group, stat = path.split(".")
        assert mix[group][stat] == cut["run"] != cut["published"]
    for r in loadgen.ClosedLoopSource(mix, 1, 1000).take(256) \
            if mix["loop"] == "closed" else []:
        assert len(r.prompt) + r.max_new_tokens <= mix["max_len"]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_aged_start_serves_the_rest_of_each_output(seed):
    """Each first request keeps its prompt, takes its share of the
    output as served tokens (the shares one per stratum, the same for
    every seed), and asks for the rest; its whole length is unchanged."""
    mix = _mix("short-prompt-long-output-closed")
    first = loadgen.ClosedLoopSource(mix, seed, 1000).take(32)
    aged = loadgen.aged(first, mix, seed, 1000)
    served = []
    for r, a in zip(first, aged):
        n = len(a.prompt) - len(r.prompt)
        assert a.rid == r.rid and np.array_equal(a.prompt[:len(r.prompt)],
                                                 r.prompt)
        assert 0 <= n < r.max_new_tokens and a.max_new_tokens == \
            r.max_new_tokens - n
        assert all(0 <= t < 1000 for t in a.prompt)
        served.append(n / r.max_new_tokens)
    strata = sorted(int(u * 32) for u in served)
    assert len(set(strata)) >= 28 and max(served) > 0.9
    other = loadgen.aged(loadgen.ClosedLoopSource(mix, seed + 1, 1000)
                         .take(32), mix, seed + 1, 1000)
    assert [(len(a.prompt), a.max_new_tokens) for a in aged] == \
        [(len(a.prompt), a.max_new_tokens) for a in other]
    assert _key(aged) == _key(loadgen.aged(first, mix, seed, 1000))
