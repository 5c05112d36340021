"""The trace reduction: by hand on a synthetic trace, and on a short
trace recorded on a TPU v5e (``chipbench/testdata``)."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, trace_reduce as tr

TESTDATA = Path(__file__).resolve().parents[1] / "testdata" / \
    "qwen05b-chat-open.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), end_ns=float(start + dur),
              duration_ns=float(dur))


def _synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev(tr.WINDOW_SPAN, 0, 1000),
        _ev("engine.step", 0, 600),
        _ev("await_arrival", 600, 400)])])
    ops = [_ev("%while.1 = (s32[]) while(...)", 100, 300),
           _ev("%fusion.2 = bf16[64,4096]{1,0} fusion(...)", 100, 100),
           _ev("%copy.3 = bf16[8]{0} copy(...)", 250, 150),
           _ev("%fusion.2 = bf16[64,4096]{1,0} fusion(...)", 450, 50),
           _ev("%late = f32[] add()", 1200, 10)]
    mods = [_ev("jit_decode_step(123)", 100, 300),
            _ev("jit_decode_step(123)", 450, 50),
            _ev("jit_argmax(9)", 1200, 10)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=mods)])
    return NS(planes=[host, NS(name="/host:metadata", lines=[]), dev])


def test_reduction_by_hand():
    s = tr.reduce_profile(_synthetic(), harness.SPANS)
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [100, 400) and [450, 500); the op at 1200 is outside
    assert s.busy_s == pytest.approx(350e-9)
    assert s.idle_share == pytest.approx(0.65)
    assert s.programs == {"jit_decode_step": pytest.approx(350e-9)}
    assert s.program_calls == {"jit_decode_step": 2}
    # the loop's own event holds the others: only the leaves count
    assert s.ops == {
        "jit_decode_step:%fusion.2 bf16[64,4096]": pytest.approx(150e-9),
        "jit_decode_step:%copy.3 bf16[8]": pytest.approx(150e-9)}
    # gaps [0,100) [400,450) in engine.step, [500,1000) mostly waiting
    assert s.idle_by_span == {"engine.step": pytest.approx(150e-9),
                              "await_arrival": pytest.approx(500e-9)}
    assert s.gaps[0] == ("await_arrival", pytest.approx(500e-9))


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_a_trace_without_its_window_is_refused():
    pd = _synthetic()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tr.reduce_profile(pd, harness.SPANS)


def test_recorded_trace():
    s = tr.reduce_file(TESTDATA, harness.SPANS)
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.program_calls["jit_decode_step"] > 0
    dev = sum(s.programs.values())
    assert dev <= s.window_s * 1.001
    assert sum(s.ops.values()) <= dev * 1.001
    assert all(k.startswith(("jit_", "?")) for k in s.ops)
    idle = sum(s.idle_by_span.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert set(s.idle_by_span) <= set(harness.SPANS) | {"host.other"}
