"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* Device planes are ``/device:TPU:<n>``. Their ``XLA Ops`` line holds
  one event per operation run, their ``XLA Modules`` line one event
  per program run (``jit_decode_step(<id>)`` and the like).
* Busy time is the union of the operation intervals inside the traced
  window, per device, averaged over the devices; idle is the rest.
* Time per program sums the module events by name (the ``(<id>)``
  suffix dropped). Time per operation sums the innermost operation
  events (a loop's own event, which holds its body's, is left out),
  each named ``<program>:<op> <shape>`` from its HLO text.
* The traced window is the host span ``WINDOW_SPAN``, which the harness
  opens right after the trace starts and closes before it stops.
* Each idle gap is named by the harness span (``engine.step``,
  ``await_arrival``, ``retire``, ...) that overlaps it most on the host;
  ``host.other`` where none does.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

__all__ = ["WINDOW_SPAN", "TraceSummary", "reduce_profile", "reduce_file",
           "union", "program_name", "op_label"]

WINDOW_SPAN = "chipbench.traced_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                           # averaged over devices
    n_devices: int
    programs: dict = field(default_factory=dict)   # name -> device seconds
    program_calls: dict = field(default_factory=dict)
    ops: dict = field(default_factory=dict)        # op name -> seconds
    idle_by_span: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)       # (span, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name).strip()


def op_label(event_name: str) -> str:
    """``%fusion.183 = bf16[64,4096]{...} fusion(...)`` ->
    ``%fusion.183 bf16[64,4096]``."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.split(" ", 1)[0].split("{", 1)[0] if rest else ""
    if len(shape) > 48:
        shape = shape[:45] + "..."
    return f"{head} {shape}".strip()


def _leaf_ops(events: list) -> list:
    """``events``: (start, end, name) sorted by start, then longest
    first. Keeps the events that hold no other."""
    out = []
    for i, (s, e, n) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < e:
            continue
        out.append((s, e, n))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``[(start, end), ...]`` into disjoint sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _overlap(a0, a1, spans):
    return sum(max(0.0, min(a1, e) - max(a0, s)) for s, e in spans)


def reduce_profile(pd, span_names: tuple[str, ...]) -> TraceSummary:
    """``pd``: a ``jax.profiler.ProfileData``. ``span_names``: the host
    spans that may name an idle gap."""
    window = None
    host_spans: dict[str, list] = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in span_names:
                    host_spans[ev.name].append((ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError("no /device:TPU plane in the trace")
    lo, hi = window
    busy_total = 0.0
    programs: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    ops: dict[str, float] = defaultdict(float)
    idle_by: dict[str, float] = defaultdict(float)
    gaps = []
    for plane in devices:
        op_ev, mod_ev = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                op_ev = [(ev.start_ns, ev.end_ns, ev.name)
                         for ev in line.events]
            elif line.name == "XLA Modules":
                mod_ev = [(ev.start_ns, ev.end_ns, program_name(ev.name))
                          for ev in line.events]
        op_ev.sort(key=lambda t: (t[0], -t[1]))
        mod_ev.sort()
        for s, e, name in mod_ev:
            if lo <= s < hi:
                programs[name] += (e - s) * 1e-9
                calls[name] += 1
        j = 0
        for s, e, name in _leaf_ops(op_ev):
            if not lo <= s < hi:
                continue
            while j < len(mod_ev) and mod_ev[j][1] <= s:
                j += 1
            prog = mod_ev[j][2] if j < len(mod_ev) and \
                mod_ev[j][0] <= s else "?"
            ops[f"{prog}:{op_label(name)}"] += (e - s) * 1e-9
        op_iv = [(s, e) for s, e, _ in op_ev]
        mod_iv = [(s, e) for s, e, _ in mod_ev]
        busy = union(_clip(op_iv or mod_iv, lo, hi))
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, label = 0.0, "host.other"
            for name, spans in host_spans.items():
                ov = _overlap(g0, g1, spans)
                if ov > best:
                    best, label = ov, name
            idle_by[label] += (g1 - g0) * 1e-9
            gaps.append((label, (g1 - g0) * 1e-9))
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        n_devices=n, programs=dict(programs), program_calls=dict(calls),
        ops=dict(ops), idle_by_span={k: v / n for k, v in idle_by.items()},
        gaps=gaps)


def reduce_file(path, span_names: tuple[str, ...]) -> TraceSummary:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(str(path)),
                          span_names)
