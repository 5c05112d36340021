"""The program's spans and counters as the benchmark reads them: idle
time named by the innermost span (by hand, and on the trace recorded on
a TPU v5e), the clocks check, and on a smoke-width cell the decode
calls the engine counts against the positions the harness infers."""

import time
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, program, spans, trace_reduce as tr
from chipbench.tests import smoke
from chipbench.tests.test_bench_trace import TESTDATA, _ev

SEED = 2 ** 31 + 91


def test_leaf_segments_name_each_piece_by_the_innermost_span():
    segs = spans.leaf_segments([
        (0, 100, "engine.step"), (10, 90, "phase_execute"),
        (20, 50, "phase_decode"), (40, 50, "phase_sync"),
        (50, 80, "phase_decode"), (120, 130, "retire")])
    assert segs == [(0, 10, "engine.step"), (10, 20, "phase_execute"),
                    (20, 40, "phase_decode"), (40, 50, "phase_sync"),
                    (50, 80, "phase_decode"), (80, 90, "phase_execute"),
                    (90, 100, "engine.step"), (120, 130, "retire")]


def _nested():
    """A window of 1000 ns: a step [0, 700) holding compose [0, 100) and
    execute [100, 700), which holds decode [100, 400) with its sync
    [300, 400) and decode [400, 700) with sync [650, 700); then waiting.
    The device runs [150, 320) and [450, 660)."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev(tr.WINDOW_SPAN, 0, 1000), _ev("engine.step", 0, 700),
        _ev("phase_compose", 0, 100), _ev("phase_execute", 100, 600),
        _ev("phase_decode", 100, 300), _ev("phase_sync", 300, 100),
        _ev("phase_decode", 400, 300), _ev("phase_sync", 650, 50),
        _ev("await_arrival", 700, 300)])])
    ops = [_ev("%fusion.1 = bf16[8]{0} fusion()", 150, 170),
           _ev("%fusion.1 = bf16[8]{0} fusion()", 450, 210)]
    mods = [_ev("jit_decode_step(1)", 150, 170),
            _ev("jit_decode_step(1)", 450, 210)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=mods)])
    return NS(planes=[host, dev])


def test_idle_is_named_by_the_innermost_span_by_hand():
    names = harness.SPANS + spans.PROGRAM_SPANS
    s = spans.reduce_spans(_nested(), names)
    # gaps: [0,150): compose 100, decode 50 -> compose;
    # [320,450): sync 80, decode 50 -> sync;
    # [660,1000): sync 40, await 300 -> await_arrival
    assert s["idle_by_leaf"] == {
        "phase_compose": pytest.approx(150e-9),
        "phase_sync": pytest.approx(130e-9),
        "await_arrival": pytest.approx(340e-9)}
    assert s["window_s"] == pytest.approx(1000e-9)
    assert (s["calls"], s["calls_within"]) == (2, 2)
    # the same gaps by the harness's rule: the whole step's share
    old = tr.reduce_profile(_nested(), harness.SPANS)
    assert old.idle_by_span == {"engine.step": pytest.approx(280e-9),
                                "await_arrival": pytest.approx(340e-9)}


def test_a_call_outside_its_span_is_not_within():
    pd = _nested()
    mods = pd.planes[1].lines[1].events
    # the slack is 50 us: this call ends 60 us after its span
    mods[1] = _ev("jit_decode_step(1)", 450, 60_250)
    s = spans.reduce_spans(pd, spans.PROGRAM_SPANS)
    assert (s["calls"], s["calls_within"]) == (2, 1)
    assert s["calls_outside"] == {"in_later_half": 0, "start_early": 0,
                                  "start_early_us_p50": None, "end_late": 1,
                                  "end_late_us_p50": pytest.approx(60.0)}
    # the second decode span now opens 60.05 us after its call starts
    pd.planes[0].lines[0].events[6] = _ev("phase_decode", 60_500, 300)
    s = spans.reduce_spans(pd, spans.PROGRAM_SPANS)
    assert s["calls_outside"]["start_early"] == 1
    assert s["calls_outside"]["start_early_us_p50"] == pytest.approx(60.05)


def test_recorded_trace_leaf_split_sums_to_the_idle_time():
    """The recorded trace holds only the harness's spans, which do not
    nest: naming a gap by its innermost span names it as before."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(TESTDATA))
    s = spans.reduce_spans(pd, harness.SPANS + spans.PROGRAM_SPANS)
    old = tr.reduce_file(TESTDATA, harness.SPANS)
    assert s["window_s"] == pytest.approx(old.window_s)
    assert sum(s["idle_by_leaf"].values()) == pytest.approx(
        old.window_s - old.busy_s, rel=1e-6)
    assert s["idle_by_leaf"].keys() == old.idle_by_span.keys()
    for k, v in old.idle_by_span.items():
        assert s["idle_by_leaf"][k] == pytest.approx(v, rel=1e-9)
    assert s["calls"] == old.program_calls["jit_decode_step"]
    assert s["calls_within"] == 0       # no program spans in that trace


def _cell(kind):
    if kind == "open":
        return smoke.cell("qwen05b-chat-open", smoke.qwen_config(),
                          smoke.open_mix())
    return smoke.cell("mixtral8x7b-l2-mixed-closed", smoke.mixtral_config(),
                      smoke.closed_mix(), smoke.MOE_LIMITS)


def _calls(eng) -> float:
    return sum(eng.metrics.counter("decode_calls", kind=k).value
               for k in ("prefill", "decode"))


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_decode_calls_equal_the_positions_the_harness_counts(kind,
                                                             monkeypatch):
    seen = {}
    build = program.build_engine

    def capture(*a, **k):
        seen["engine"] = build(*a, **k)
        return seen["engine"]

    class Calls(harness._Calls):
        def __init__(self):             # made after the warm-up
            super().__init__()
            seen["calls"], seen["c0"] = self, _calls(seen["engine"])

    monkeypatch.setattr(program, "build_engine", capture)
    monkeypatch.setattr(harness, "_Calls", Calls)
    run = harness.run_cell(_cell(kind), SEED, 1.5, False,
                           time.perf_counter())
    eng = seen["engine"]
    assert run.correct
    assert _calls(eng) - seen["c0"] == len(seen["calls"].positions) > 0
    emitted = eng.metrics.counter("tokens_emitted").value
    assert emitted >= run.tokens > 0


def test_recorder_splits_steps_and_lags_tokens():
    cfg = program.model_config(smoke.qwen_config(), "smoke")
    params = program.make_params(cfg, SEED)
    rec = spans.Recorder(spans.PROGRAM_SPANS)
    eng = rec.engine(program.build_engine)(cfg, params, 64)
    from repro.serve import Request
    reqs = [Request(i, [1 + i] * (3 + i), max_new_tokens=4)
            for i in range(3)]
    eng.submit(reqs)
    while eng.step():
        pass
    made = sum(len(r.generated) for r in reqs)
    assert rec.lagged == made and rec.lag_s >= 0.0
    for d, _, prefill, decode, sync in rec.steps:
        assert 0.0 <= sync <= prefill + decode <= d
    snap = rec.counters(harness._counters)(eng.metrics)
    assert snap["calls_prefill"] == sum(len(r.prompt) for r in reqs)
    assert snap["tokens_emitted"] == made
    assert snap["queue_n"] == len(reqs)


def test_measure_reports_the_longest_steps_split():
    run, out = spans.measure(_cell("open"), SEED, 1.5, False,
                             time.perf_counter())
    assert run.correct and "counters" not in out    # untraced: no window
    steps = out["longest_steps"]
    assert 0 < len(steps) <= 5
    assert steps == sorted(steps)
    for d, at, prefill, decode, sync in steps:
        assert at >= 0.0 and 0.0 <= sync <= prefill + decode <= d
