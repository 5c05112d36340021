"""Drive one cell once: set up, measure a window, check, report.

The program is driven through ``ServingEngine.submit`` and
``ServingEngine.step``, by the host clock:

* open loop: every request due by now is submitted, then one
  ``step()`` runs; with nothing live, the loop sleeps to the next due
  time;
* closed loop: a finished request is replaced at once, so
  ``concurrency`` requests are always in flight.

The engine has no per-token callback. After each ``step()`` the new
tokens of each request are stamped with the time the step returned.
Finished requests are taken out of ``engine.queue`` and their caches
dropped (the engine keeps every request it was given).

Latency counts from each request's due time. A request due in the
window with no first token when the window closes enters the
time-to-first-token tail with its elapsed time, and a live request's
open gap enters the token-gap tail the same way, so a stall cannot
hide.

A traced run profiles a sub-window of the window. The profiler's stop
writes the trace out, which takes tens of seconds on the chip; that
interval is left out of the window's clock, so the steps still run for
the window's length and the requests the check compares still finish.
"""

from __future__ import annotations

import collections
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from . import check, counts, loadgen, program, spec, trace_reduce

__all__ = ["SPANS", "Run", "run_cell", "call_work", "read_metrics",
           "percentile"]

#: host spans written into the profiler trace; they name idle gaps
SPANS = ("engine.step", "await_arrival", "retire", "submit")


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation (numpy's
    default); ``nan`` for no values."""
    return float(np.percentile(values, q)) if len(values) else float("nan")


def _say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


@dataclass
class Run:
    """What one run measured, before it is turned into metrics."""
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    ttft_s: list = field(default_factory=list)
    itl_s: list = field(default_factory=list)
    tokens: int = 0
    late_s: list = field(default_factory=list)
    compiles_in_window: int = 0
    peak_bytes: int | None = None
    breakdown: dict | None = None
    busy_s: float | None = None
    traced_window_s: float | None = None
    checks: dict = field(default_factory=dict)
    correct: bool = False
    #: (window time, requests submitted and not finished) after each step
    backlog: list = field(default_factory=list)
    #: positions held in the live requests' caches over the positions
    #: their caches reserve (``max_len`` each), summed over the window's
    #: steps
    kv_in_use: float | None = None
    #: the window's five longest steps: (seconds, start in the window,
    #: live requests, decode calls, compose s, execute s)
    longest_steps: list = field(default_factory=list)
    #: what the metric readers read: ``run`` (this object) and, in a
    #: traced run, ``trace``, ``counters``, ``positions``, ``work``
    #: (:func:`call_work`) and ``peaks``
    ctx: dict = field(default_factory=dict)


class _CompileCounter:
    """Counts backend compilations while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, *_a, **_k):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class _Calls:
    """The positions of the decode calls a step made, read from how each
    request's state moved (a prefill replays positions 0..S-1, a decode
    step runs at the request's position)."""

    def __init__(self):
        self.positions: list[int] = []

    @staticmethod
    def before(reqs) -> list:
        return [(r, r.cache is None, r.pos) for r in reqs]

    def after(self, snap) -> None:
        for r, fresh, pos in snap:
            if fresh and r.cache is not None:
                self.positions.extend(range(len(r.prompt)))
            elif not fresh and r.pos != pos:
                self.positions.append(pos)


def _warm(engine, prompt_lens) -> None:
    """Compile every program the window will run: the decode step, the
    argmax and its transfer, and the per-length slicing of a replayed
    prompt, for every prompt length the traffic sends."""
    from repro.serve import Request
    for s in prompt_lens:
        toks = jnp.asarray(np.zeros(s, np.int32), jnp.int32)[None, :]
        jax.block_until_ready(toks[:, 0])
    warm = Request(-1, np.zeros(min(prompt_lens), np.int32),
                   max_new_tokens=2)
    engine.submit([warm])
    while not warm.done:
        engine.step()
    engine.queue.remove(warm)


class _Tracer:
    """The profiler over one sub-window of steps, opened and closed at
    step boundaries."""

    def __init__(self, start_s: float, length_s: float):
        self.start_s, self.end_s = start_s, start_s + length_s
        self.dir = None
        self.state = "before"
        self.span = None

    def at(self, now: float, metrics, calls: _Calls) -> None:
        if self.state == "before" and now >= self.start_s:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # Python calls: not traced
            opts.host_tracer_level = 1        # the harness's spans only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self.span.__enter__()
            self.state = "on"
            self.c0 = _counters(metrics)
            self.p0 = len(calls.positions)
        elif self.state == "on" and now >= self.end_s:
            self.stop(metrics, calls)

    def stop(self, metrics, calls: _Calls) -> None:
        if self.state != "on":
            return
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        c1 = _counters(metrics)
        self.counters = {k: c1[k] - self.c0[k] for k in c1}
        self.positions = calls.positions[self.p0:]
        self.state = "done"

    def reduce(self) -> trace_reduce.TraceSummary:
        paths = [os.path.join(d, f) for d, _, fs in os.walk(self.dir)
                 for f in fs if f.endswith(".xplane.pb")]
        try:
            return trace_reduce.reduce_file(paths[0], SPANS)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _counters(metrics) -> dict:
    return {"engine_steps": metrics.counter("engine_steps").value,
            "phase_compose_s": metrics.histogram("phase_compose").total,
            "phase_execute_s": metrics.histogram("phase_execute").total}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device=None) -> Run:
    """One run of ``cell``. ``t_start``: ``time.perf_counter()`` when
    the process started; set-up counts from there."""
    from repro.serve import Request as EngineRequest

    c, mix = cell.config, cell.traffic
    cfg = program.model_config(c, cell.config_name, cell.bench_dir)
    max_len = int(mix["max_len"])
    vocab = cfg.vocab
    device = device or jax.devices()[0]
    closed = mix["loop"] == "closed"

    if closed:
        source = loadgen.ClosedLoopSource(mix, seed, vocab)
        epoch = loadgen.ClosedLoopSource(mix, seed, vocab).take(
            int(mix["requests_per_epoch"]))
        prompt_lens = sorted({len(r.prompt) for r in epoch})
    else:
        offered = loadgen.open_loop(mix, seed, seconds, vocab)
        prompt_lens = sorted({len(r.prompt) for r in offered})

    params = jax.block_until_ready(program.make_params(cfg, seed))
    engine = program.build_engine(cfg, params, max_len)
    _warm(engine, prompt_lens)

    live: dict[int, tuple] = {}        # rid -> (offered, engine request)
    stamps: dict[int, list] = {}       # rid -> token times (perf_counter)
    finished: list[check.Served] = []
    calls = _Calls()

    def submit(batch):
        reqs = []
        for o in batch:
            r = EngineRequest(o.rid, o.prompt, max_new_tokens=o.max_new_tokens)
            live[o.rid] = (o, r)
            stamps[o.rid] = []
            reqs.append(r)
        engine.submit(reqs)

    steps: list[tuple] = []

    def step_and_stamp():
        snap = calls.before([r for _, r in live.values()])
        c0, n0 = _counters(engine.metrics), len(calls.positions)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.step"):
            engine.step()
        t = time.perf_counter()
        calls.after(snap)
        c1 = _counters(engine.metrics)
        steps.append((t - t0, t0, len(live), len(calls.positions) - n0,
                      c1["phase_compose_s"] - c0["phase_compose_s"],
                      c1["phase_execute_s"] - c0["phase_execute_s"]))
        n = 0
        for rid, (_, r) in live.items():
            new = len(r.generated) - len(stamps[rid])
            stamps[rid].extend([t] * new)
            n += new
        return t, n

    def retire():
        done = [rid for rid, (_, r) in live.items() if r.done]
        if done:
            with jax.profiler.TraceAnnotation("retire"):
                for rid in done:
                    o, r = live.pop(rid)
                    finished.append(check.Served(rid, o.prompt, r.generated))
                    r.cache = None
                engine.queue = [r for r in engine.queue if not r.done]

    if closed:
        conc = int(mix["concurrency"])
        first = source.take(conc)
        if mix.get("aged_start"):
            first = loadgen.aged(first, mix, seed, vocab)
        submit(first)
        _say(f"prefilling {conc} requests in set-up "
             f"({sum(len(o.prompt) for o in first)} positions)")
        step_and_stamp()

    counter = _CompileCounter()
    tracer = _Tracer(float(mix["trace"]["start_s"]),
                     float(mix["trace"]["length_s"])) if trace else None
    tokens_in_window = 0
    kv_used = kv_held = 0
    backlog: list[tuple] = []
    late: list[float] = []
    pending = collections.deque() if closed else collections.deque(offered)
    setup_s = time.perf_counter() - t_start
    _say(f"set-up {setup_s:.3f}s; window {seconds}s")

    counter.on = True
    clock0 = time.perf_counter()
    # the profiler's stop writes the trace out, for tens of seconds, in
    # the middle of the window; that interval is no window time
    paused, paused_until = 0.0, math.inf

    def window_time(t: float) -> float:
        return t - clock0 - (paused if t >= paused_until else 0.0)

    now = 0.0
    while now < seconds:
        if tracer and tracer.state != "done":
            t0 = time.perf_counter()
            tracer.at(now, engine.metrics, calls)
            if tracer.state == "done":
                paused_until = time.perf_counter()
                paused = paused_until - t0
        if closed:
            with jax.profiler.TraceAnnotation("submit"):
                submit(source.take(int(mix["concurrency"]) - len(live)))
        else:
            due = []
            while pending and pending[0].due <= now:
                due.append(pending.popleft())
            if due:
                with jax.profiler.TraceAnnotation("submit"):
                    submit(due)
                    t_sub = window_time(time.perf_counter())
                    late.extend(t_sub - o.due for o in due)
            if not live:
                nxt = pending[0].due if pending else seconds
                with jax.profiler.TraceAnnotation("await_arrival"):
                    time.sleep(max(0.0, min(nxt, seconds) - now))
                now = window_time(time.perf_counter())
                continue
        t, n = step_and_stamp()
        now = window_time(t)
        tokens_in_window += n
        retire()
        backlog.append((now, len(live)))
        kv_used += sum(r.pos for _, r in live.values())
        kv_held += len(live) * max_len
    window_s = now
    if tracer:
        tracer.stop(engine.metrics, calls)
    counter.on = False

    run = Run(setup_s=setup_s, window_s=window_s, attempted=len(stamps),
              failed=0, tokens=tokens_in_window, late_s=late,
              compiles_in_window=counter.n, backlog=backlog,
              kv_in_use=kv_used / kv_held if kv_held else None)
    run.longest_steps = [(d, window_time(t0), *rest) for d, t0, *rest in
                         sorted(s for s in steps if s[1] >= clock0)[-5:]]
    run.ctx["run"] = run
    # -- latency from due time; what never came counts as waited -------
    rel = {rid: [window_time(t) for t in s] for rid, s in stamps.items()}
    if not closed:
        due_in = [o for o in offered if o.due < seconds]
        run.attempted = len(due_in)
        run.ttft_s = [(rel[o.rid][0] if rel.get(o.rid) else window_s) - o.due
                      for o in due_in]
    for rid, s in rel.items():
        run.itl_s.extend(b - a for a, b in zip(s, s[1:]) if a >= 0.0)
        if rid in live and s:
            run.itl_s.append(window_s - max(s[-1], 0.0))
    stats = device.memory_stats() or {}
    run.peak_bytes = stats.get("peak_bytes_in_use")

    # -- correctness: the reference over a sample of finished requests --
    for _, r in live.values():
        r.cache = None
    engine.queue.clear()
    del engine
    run.failed = sum(1 for f in finished
                     if any(not 0 <= t < vocab for t in f.tokens))
    chk = mix["check"]
    picked = check.sample(finished, seed, int(chk["min_tokens"]),
                          int(chk["max_requests"]))
    numbers = {"tokens_compared": sum(len(f.tokens) for f in picked)}
    if picked and not run.failed:
        t0 = time.perf_counter()
        ref = check.reference_for(
            c, program.reference_weights(params, cfg, c, cell.bench_dir),
            cell.bench_dir)
        g = check.gaps(ref, picked, max_len)
        run.ctx.update(reference=ref, picked=picked, length=max_len, gaps=g)
        numbers.update(check.gap_numbers(g))
        _say(f"reference over {len(picked)} requests, "
             f"{numbers['tokens_compared']} tokens: "
             f"{time.perf_counter() - t0:.3f}s; {numbers}")
    run.correct, run.checks = check.verdict(numbers, cell.limits["checks"])

    if tracer and tracer.state == "done":
        _trace_context(run, tracer, cell, device)
    return run


def call_work(cell: spec.Cell) -> counts.Work:
    """The work of the cell's served calls, as its family counts it."""
    return check.family(cell.config, cell.bench_dir).work(cell.config)


def _trace_context(run: Run, tracer: _Tracer, cell: spec.Cell,
                   device) -> None:
    summary = tracer.reduce()
    run.ctx.update({"trace": summary, "counters": tracer.counters,
                    "positions": tracer.positions,
                    "work": call_work(cell),
                    "peaks": counts.peaks_for(device.device_kind)})
    run.busy_s = summary.busy_s
    run.traced_window_s = summary.window_s
    top_ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:10]
    by_span = sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])
    run.breakdown = {"device_ops": [[k, v] for k, v in top_ops],
                     "idle_gaps": [[k, v] for k, v in by_span[:10]]}
    progs = sorted(summary.programs.items(), key=lambda kv: -kv[1])
    _say(f"device programs (s, calls): "
         f"{[(k, v, summary.program_calls[k]) for k, v in progs[:10]]}")
    _say(f"longest idle gaps (span, s): {summary.gaps[:10]}")


def read_metrics(entries: list, run: Run,
                 bench_dir=spec.BENCH_DIR) -> dict:
    """Each metric of ``entries`` by its reader,
    ``chipbench/metrics/<name>.py``; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in entries:
        reader = spec.load_module(spec.metric_reader_path(m["name"],
                                                          bench_dir))
        v = reader.read(run.ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
