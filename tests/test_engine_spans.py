"""The serving engine's spans and counters, read back from a profiler
trace taken on the CPU around a smoke-width engine: every timer is a
host span of its phase's name, nested compose / execute > prefill |
decode > sync, carrying its request's id; each histogram's total is
the sum of its spans; the counters count what was run; and served
tokens do not depend on whether the profiler runs."""

import os
import re
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.obs import LatencyTracker, MetricsRegistry
from repro.serve import Request, ServingEngine

PHASE_SPANS = ("phase_compose", "phase_execute", "phase_prefill",
               "phase_decode", "phase_sync")
PROMPT_LENS = (5, 3, 7)
NEW_TOKENS = 4


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen1.5-0.5b", "smoke")
    return cfg, T.init(jax.random.PRNGKey(0), cfg)


def _serve(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, max_len=32)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab, size=n),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]
    eng.submit(reqs)
    while eng.step():
        pass
    return eng, reqs


def _profiled(model, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng, reqs = _serve(model)
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    spans = defaultdict(list)          # name -> [(start, end, stats)]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("phase_"):
                    spans[ev.name].append((ev.start_ns, ev.end_ns,
                                           dict(ev.stats)))
    return eng, reqs, spans


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    return _profiled(model, tmp_path_factory.mktemp("trace"))


def _inside(span, outers) -> bool:
    s, e, _ = span
    return any(s0 <= s and e <= e0 for s0, e0, _ in outers)


def test_host_plane_holds_each_phase_nested_with_its_request(traced):
    eng, reqs, spans = traced
    assert set(PHASE_SPANS) <= set(spans)
    execute = spans["phase_execute"]
    per_call = spans["phase_prefill"] + spans["phase_decode"]
    assert all(_inside(s, execute) for s in per_call)
    assert all(_inside(s, per_call) for s in spans["phase_sync"])
    assert not any(_inside(s, spans["phase_compose"]) for s in per_call)
    rids = {r.rid for r in reqs}
    for name in ("phase_prefill", "phase_decode", "phase_sync"):
        assert {st["rid"] for _, _, st in spans[name]} == rids
    assert sorted(st["prompt_len"] for _, _, st in spans["phase_prefill"]) \
        == sorted(PROMPT_LENS)
    by_rid = defaultdict(list)
    for s, _, st in sorted(spans["phase_decode"], key=lambda x: x[0]):
        by_rid[st["rid"]].append(st["pos"])
    for r in reqs:
        n = len(r.prompt)
        assert by_rid[r.rid] == list(range(n, n + NEW_TOKENS - 1))
    # compose and execute are per step, with no request on them
    assert not any("rid" in st for _, _, st in execute)


def test_each_histogram_total_is_the_sum_of_its_spans(traced):
    eng, _, spans = traced
    for name in PHASE_SPANS:
        h = eng.metrics.histogram(name)
        assert h.count == len(spans[name]), name
        dur = sum(e - s for s, e, _ in spans[name]) * 1e-9
        assert h.total == pytest.approx(dur, rel=0.05), name


def test_counters_count_calls_and_tokens(traced):
    eng, reqs, spans = traced
    snap = eng.metrics.snapshot()
    assert snap["decode_calls{kind=prefill}"] == sum(PROMPT_LENS)
    assert snap["decode_calls{kind=decode}"] == \
        len(spans["phase_decode"]) == len(reqs) * (NEW_TOKENS - 1)
    made = sum(len(r.generated) for r in reqs)
    assert snap["tokens_emitted"] == made == len(spans["phase_sync"])
    for r in reqs:
        assert len(r.token_times) == len(r.generated)
        assert r.token_times == sorted(r.token_times)
    # registry labels stay one series per phase: no per-request series
    assert not any("rid" in k for k in snap)


def test_tokens_are_bit_identical_with_the_profiler_on_and_off(model,
                                                               traced):
    _, on, _ = traced
    _, off = _serve(model)
    assert [r.generated for r in on] == [r.generated for r in off]


def test_queue_closes_at_prefill_start_and_execute_is_each_requests_own(
        model):
    cfg, params = model
    eng = ServingEngine(cfg, params, max_len=32)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab, size=n),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]
    eng.submit(reqs)
    eng.step()                        # every request prefills here
    m = eng.metrics
    first_step = (m.histogram("phase_compose").total +
                  m.histogram("phase_execute").total)
    while eng.step():
        pass
    # the wait is observed once per request, when its prefill starts:
    # the last one served waited for the others' prefills, not for the
    # end of the step
    q = m.histogram("request_queue_s")
    assert q.count == len(reqs)
    assert q.vmax < first_step - m.histogram("phase_prefill").vmin
    # each request's execute share is its own prefill and decode time
    own = m.histogram("request_phase_s", phase="execute").total
    calls = m.histogram("phase_prefill").total + \
        m.histogram("phase_decode").total
    assert own == pytest.approx(calls, rel=1e-9)
    assert own < m.histogram("phase_execute").total
    # finished spans are dropped, so a reused request id completes again
    assert not eng.latency._open
    again = Request(reqs[0].rid, reqs[0].prompt, max_new_tokens=2)
    eng.submit([again])
    while eng.step():
        pass
    assert m.counter("requests_completed").value == len(reqs) + 1
    assert again.generated == reqs[0].generated[:2]


def test_latency_tracker_start_and_charge():
    t = {"now": 0.0}
    lt = LatencyTracker(MetricsRegistry(), clock=lambda: t["now"])
    lt.arrive(1, t=0.0)
    lt.start(1, t=1.5)
    lt.start(1, t=9.0)                # already started: no second wait
    lt.charge(1, "execute", 0.25)
    lt.charge(1, "execute", 0.5)
    lt.attribute([1], {"compose": 0.5}, t=3.0)
    lt.complete(1, tokens=2, t=4.0)
    lt.complete(1, tokens=2, t=5.0)   # already closed: ignored
    q = lt.metrics.histogram("request_queue_s")
    assert (q.count, q.total) == (1, 1.5)
    st = lt.stats(wall_s=4.0)
    assert st["completed"] == 1 and st["in_flight"] == 0
    assert st["phase_mean_s"]["execute"] == pytest.approx(0.75)
    assert st["phase_mean_s"]["compose"] == pytest.approx(0.5)


def test_timer_metadata_rides_on_the_span_not_a_label():
    m = MetricsRegistry()
    with m.timer("phase_x") as t:
        pass
    with m.histogram("phase_x").time(rid=8, pos=3):
        pass
    h = m.histogram("phase_x")
    assert h.count == 2 and t.elapsed >= 0.0
    assert sorted(m.snapshot()) == sorted(
        f"phase_x.{k}" for k in ("count", "total_s", "mean_s", "min_s",
                                 "max_s", "p50_s", "p95_s", "p99_s"))


@pytest.mark.parametrize("arch,path,n_moe", [
    ("mixtral-8x7b", "gathered", 4),     # every layer routes, top-2 of 4
    ("jamba-v0.1-52b", "gathered", 4),   # every other layer of 8
    ("qwen1.5-0.5b", None, 0),           # dense: nothing to count
])
def test_moe_dispatch_counts_each_calls_moe_layers(arch, path, n_moe):
    cfg = get_config(arch, "smoke")
    assert T.n_moe_layers(cfg) == n_moe
    eng = ServingEngine(cfg, T.init(jax.random.PRNGKey(0), cfg), max_len=16)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, size=3)
    eng.submit([Request(0, prompt, max_new_tokens=3)])
    while eng.step():
        pass
    snap = eng.metrics.snapshot()
    calls = snap["decode_calls{kind=prefill}"] + \
        snap["decode_calls{kind=decode}"]
    assert calls == 3 + 2
    counted = {k: v for k, v in snap.items() if k.startswith("moe_dispatch")}
    if path is None:
        assert counted == {}
    else:
        assert counted == {f"moe_dispatch{{path={path}}}": calls * n_moe}


def _deepseek_toy():
    """DeepSeek-V2's smoke model cut to one chip's share as its
    benchmark configuration is: a leading dense layer and 4 MoE layers,
    a router over 40 experts in 8 groups of 5, top-4, group 0 held."""
    return get_config("deepseek-v2-236b", "smoke").replace(
        n_layers=5, n_experts=40, experts_held=5, top_k=4, moe_d_ff=32)


MLA_MOE_SCOPES = ("mla.absorb_q", "mla.latent_scores", "mla.absorb_v",
                  "moe.route_group_limited", "moe.held_experts",
                  "moe.shared")


def test_decode_program_names_its_latent_attention_and_experts():
    """The device ops of the served decode step carry the named scopes
    of the absorbed attention and of the held share's MoE layer."""
    cfg = _deepseek_toy()
    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 1, 16))
    hlo = jax.jit(T.decode_step, static_argnums=(1,)).lower(
        params, cfg, np.zeros(1, np.int32), cache, 3).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in MLA_MOE_SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope


def test_held_share_gauges_and_dispatch_count():
    """The engine states the experts a layer holds and the router's
    width once, and counts each call's 4 MoE layers under the gathered
    path a one-token call of the held share takes."""
    cfg = _deepseek_toy()
    params = jax.jit(T.init, static_argnums=(1,))(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=16)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, size=3)
    eng.submit([Request(0, prompt, max_new_tokens=3)])
    while eng.step():
        pass
    snap = eng.metrics.snapshot()
    assert snap["moe_experts_held"] == 5
    assert snap["moe_router_experts"] == 40
    calls = snap["decode_calls{kind=prefill}"] + \
        snap["decode_calls{kind=decode}"]
    assert calls == 3 + 2
    assert snap["moe_dispatch{path=gathered}"] == 4 * calls
