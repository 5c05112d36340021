"""The program's own spans and counters over a cell's traced window.

    python3 chipbench/spans.py --workload <name> --seed <n> \\
        --seconds <s> [--trace 0|1]

Runs one cell as ``run.py`` does and prints ``run.py``'s result line
with a ``spans`` block added. The engine times each request's replayed
prompt (``phase_prefill``), each decode call (``phase_decode``) and each
argmax read-back (``phase_sync``) as profiler spans, counts its decode
calls (``decode_calls{kind=...}``) and emitted tokens, stamps each token
with the time it was made (``Request.token_times``) and observes each
request's wait from ``submit()`` to its prefill (``request_queue_s``).
``harness.py`` reads none of these yet: this tool runs the harness with
its window snapshot, its steps and its trace reduction wrapped, and
reports, over the traced window:

* ``idle_by_leaf``: the device's idle time, each gap named by the
  innermost host span that covers most of it (the harness's spans and
  the program's ``phase_*`` spans), and ``idle_in_dispatch_pct``, the
  share of the window idle under ``phase_prefill``, ``phase_decode``
  or ``phase_sync``;
* ``calls_within``: how many ``jit_decode_step`` device events lie
  inside a ``phase_prefill`` or ``phase_decode`` host span widened by
  50 us at each end (the two clocks agree), and for the others how far
  they start before or end after their span, and how many lie in the
  window's later half (a drift between the clocks);
* per call, the prefill and decode milliseconds, the queue wait, and
  the emit lag: how long a token waited in its step after it was made;
* with ``--trace 0`` too, the window's five longest steps with their
  prefill, decode and sync seconds.

Against a program without those instruments the counts read zero and
the span names are absent; nothing raises.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import bisect  # noqa: E402
import heapq  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from unittest import mock  # noqa: E402

_HERE = Path(__file__).resolve().parent
for p in (str(_HERE.parent / "src"), str(_HERE.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import trace_reduce  # noqa: E402

__all__ = ["PROGRAM_SPANS", "DISPATCH", "leaf_segments", "reduce_spans",
           "Recorder", "measure"]

#: the program's phase spans (``repro.obs.profile``)
PROGRAM_SPANS = ("phase_compose", "phase_guard", "phase_refine",
                 "phase_audit", "phase_execute", "phase_prefill",
                 "phase_decode", "phase_sync")
#: the per-call dispatch and read-back spans
DISPATCH = ("phase_prefill", "phase_decode", "phase_sync")
#: the spans that hold a decode call's device program
CALL_SPANS = ("phase_prefill", "phase_decode")
PROGRAM = "jit_decode_step"
SLACK_NS = 50_000


def leaf_segments(spans: list) -> list:
    """``spans``: ``[(start, end, name)]``. The timeline cut wherever a
    span starts or ends, each piece named by the innermost span open
    over it (the one that started last; of two that started together,
    the shorter); time under no span is left out. Sorted, disjoint."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    order = sorted(spans, key=lambda sp: sp[0])
    heap, out, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k][0] <= a:
            s, e, name = order[k]
            heapq.heappush(heap, (-s, e, k, name))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return [tuple(x) for x in out]


def _leaf_of(g0, g1, segs, ends) -> str:
    over = defaultdict(float)
    i = bisect.bisect_right(ends, g0)
    while i < len(segs) and segs[i][0] < g1:
        s, e, name = segs[i]
        over[name] += min(e, g1) - max(s, g0)
        i += 1
    return max(over, key=over.get) if over else "host.other"


def _outside(s, e, spans, starts) -> tuple:
    """How far the device event ``[s, e)`` starts before and ends after
    the host span that holds it best, ns (0 where it does not)."""
    i = bisect.bisect_right(starts, s)
    near = spans[max(0, i - 2):i + 1]
    if not near:
        return e - s, e - s
    return min(((max(0, a - s), max(0, e - b)) for a, b in near),
               key=lambda v: max(v))


def reduce_spans(pd, names: tuple[str, ...]) -> dict:
    """``pd``: a ``jax.profiler.ProfileData``; ``names``: the host spans
    that may name an idle gap. Busy and idle are as
    :func:`chipbench.trace_reduce.reduce_profile` has them; each gap is
    named by :func:`leaf_segments`' piece that covers most of it."""
    window, host, devices = None, [], []
    for plane in pd.planes:
        if trace_reduce._DEVICE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in names:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None or not devices:
        raise ValueError("no traced window or no device plane")
    lo, hi = window
    segs = leaf_segments(host)
    ends = [e for _, e, _ in segs]
    calls = sorted((s, e) for s, e, n in host if n in CALL_SPANS)
    starts = [s for s, _ in calls]
    idle = defaultdict(float)
    n_calls = n_within = 0
    early, late, later_half = [], [], 0
    for plane in devices:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(ev.start_ns, ev.end_ns) for ev in line.events]
            elif line.name == "XLA Modules":
                mods = [(ev.start_ns, ev.end_ns,
                         trace_reduce.program_name(ev.name))
                        for ev in line.events]
        busy = trace_reduce.union(trace_reduce._clip(
            ops or [(s, e) for s, e, _ in mods], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                idle[_leaf_of(g0, g1, segs, ends)] += (g1 - g0) * 1e-9
        for s, e, name in mods:
            if name == PROGRAM and lo <= s < hi:
                n_calls += 1
                before, after = _outside(s, e, calls, starts)
                if max(before, after) <= SLACK_NS:
                    n_within += 1
                    continue
                later_half += s - lo >= (hi - lo) / 2
                if before > SLACK_NS:
                    early.append(before * 1e-3)
                if after > SLACK_NS:
                    late.append(after * 1e-3)
    n = len(devices)
    return {"window_s": (hi - lo) * 1e-9,
            "idle_by_leaf": {k: v / n for k, v in idle.items()},
            "calls": n_calls, "calls_within": n_within,
            "calls_outside": {"in_later_half": later_half,
                              "start_early": len(early),
                              "start_early_us_p50": _median(early),
                              "end_late": len(late),
                              "end_late_us_p50": _median(late)}}


def _median(xs):
    return sorted(xs)[len(xs) // 2] if xs else None


def _counters(metrics) -> dict:
    """The program's totals the harness does not snapshot."""
    h, c = metrics.histogram, metrics.counter
    out = {f"{ph}_s": h(ph).total for ph in DISPATCH}
    out.update(calls_prefill=c("decode_calls", kind="prefill").value,
               calls_decode=c("decode_calls", kind="decode").value,
               tokens_emitted=c("tokens_emitted").value,
               queue_s=h("request_queue_s").total,
               queue_n=h("request_queue_s").count)
    return out


class Recorder:
    """Wraps the harness: its window snapshot gains the program's
    totals and the emit lags, each ``step()`` is recorded with its
    prefill, decode and sync seconds, and the trace is reduced by
    :func:`reduce_spans` before the harness deletes it."""

    def __init__(self, names: tuple[str, ...]):
        self.names = names
        self.steps = []          # (seconds, start, prefill, decode, sync)
        self.lag_s = 0.0
        self.lagged = 0
        self.spans = None

    def counters(self, base):
        def snap(metrics):
            return dict(base(metrics), **_counters(metrics),
                        emit_lag_s=self.lag_s, tokens_lagged=self.lagged)
        return snap

    def engine(self, build):
        def wrapped(*a, **k):
            eng = build(*a, **k)
            step, seen = eng.step, {}

            def timed_step():
                c0, t0 = _counters(eng.metrics), time.perf_counter()
                n = step()
                t = time.perf_counter()
                c1 = _counters(eng.metrics)
                self.steps.append((t - t0, t0, *(c1[f"{ph}_s"] - c0[f"{ph}_s"]
                                                 for ph in DISPATCH)))
                for r in eng.queue:
                    made = getattr(r, "token_times", ())
                    new = made[seen.get(r.rid, 0):]
                    seen[r.rid] = len(made)
                    self.lag_s += sum(t - x for x in new)
                    self.lagged += len(new)
                return n
            eng.step = timed_step
            return eng
        return wrapped

    def reducer(self, base):
        def reduce(path, span_names):
            import jax
            self.spans = reduce_spans(jax.profiler.ProfileData.from_file(
                str(path)), self.names)
            return base(path, span_names)
        return reduce


def measure(cell, seed: int, seconds: float, trace: bool, t_start: float,
            device=None):
    """``harness.run_cell`` under a :class:`Recorder`; returns the run
    and the ``spans`` block."""
    from chipbench import harness, program
    rec = Recorder(harness.SPANS + PROGRAM_SPANS)
    with mock.patch.object(harness, "_counters",
                           rec.counters(harness._counters)), \
            mock.patch.object(program, "build_engine",
                              rec.engine(program.build_engine)), \
            mock.patch.object(trace_reduce, "reduce_file",
                              rec.reducer(trace_reduce.reduce_file)):
        run = harness.run_cell(cell, seed, seconds, trace, t_start,
                               device=device)
    opened = t_start + run.setup_s
    steps = sorted(s for s in rec.steps if s[1] >= opened)[-5:]
    out = {"longest_steps": [[d, t0 - opened, *rest]
                             for d, t0, *rest in steps]}
    c, pos = run.ctx.get("counters"), run.ctx.get("positions")
    if c is not None:
        out.update(counters=c, positions=len(pos), **_per_call(c, len(pos)))
    if rec.spans:
        out.update(rec.spans, **_idle_split(rec.spans))
    return run, out


def _ms(num: float, den: float):
    return 1e3 * num / den if den else None


def _per_call(c: dict, positions: int) -> dict:
    """What the window's counter deltas ``c`` give per call and token."""
    calls_s = c["phase_prefill_s"] + c["phase_decode_s"]
    return {
        "prefill_ms_per_token": _ms(c["phase_prefill_s"], c["calls_prefill"]),
        "decode_ms_per_token": _ms(c["phase_decode_s"], c["calls_decode"]),
        # a program without the prefill counter observes its queue wait
        # elsewhere (at completion): not this quantity
        "queue_wait_ms": _ms(c["queue_s"], c["queue_n"])
        if c["calls_prefill"] else None,
        "emit_lag_ms": _ms(c["emit_lag_s"], c["tokens_lagged"]),
        "calls_match_positions":
            c["calls_prefill"] + c["calls_decode"] == positions,
        "dispatch_share_of_execute": calls_s / c["phase_execute_s"]
        if c["phase_execute_s"] else None}


def _idle_split(sp: dict) -> dict:
    """The window's idle share under the per-call spans, and the part of
    the idle time inside a step that no program span names."""
    leaf = sp["idle_by_leaf"]
    in_step = sum(v for k, v in leaf.items()
                  if k == "engine.step" or k in PROGRAM_SPANS)
    return {"idle_in_dispatch_pct": 100.0 * sum(
                leaf.get(k, 0.0) for k in DISPATCH) / sp["window_s"],
            "engine_step_share_of_step_idle":
                leaf.get("engine.step", 0.0) / in_step if in_step else None}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    import jax
    from chipbench import harness, spec
    from chipbench.run import _enable_cache, result_line
    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("spans: needs the cell's TPU chips", file=sys.stderr)
        return 2
    _enable_cache(jax)
    run, spans = measure(cell, args.seed, args.seconds, bool(args.trace),
                         _T_START, device=devices[0])
    harness._say("longest steps (s, at s, prefill s, decode s, sync s): " +
                 str([[round(x, 4) for x in st]
                      for st in spans["longest_steps"]]))
    line = result_line(cell, run, bool(args.trace), devices[0],
                       len(devices))
    line["spans"] = spans
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
