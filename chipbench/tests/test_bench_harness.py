"""The harness end to end at smoke width on the CPU, through its
functions (the CLI itself refuses to run without a TPU): both loops,
the correctness check, the control, and planted faults."""

import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from chipbench import check, harness, run as run_cli
from chipbench.tests import smoke

SEED = 2 ** 31 + 77


def _cell(kind):
    if kind == "open":
        return smoke.cell("qwen05b-chat-open", smoke.qwen_config(),
                          smoke.open_mix())
    if kind == "decode":
        return smoke.cell("qwen05b-decode-closed", smoke.qwen_config(),
                          smoke.decode_mix(), smoke.DECODE_LIMITS)
    return smoke.cell("mixtral8x7b-l2-mixed-closed", smoke.mixtral_config(),
                      smoke.closed_mix(), smoke.MOE_LIMITS)


def _run(kind, seconds=2.0):
    return harness.run_cell(_cell(kind), SEED, seconds, False,
                            time.perf_counter())


@pytest.mark.parametrize("kind", ["open", "closed", "decode"])
def test_run_reports_contract_line(kind):
    cell = _cell(kind)
    run = _run(kind)
    line = run_cli.result_line(cell, run, False, jax.devices()[0], 1)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == names
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert run.compiles_in_window == 0
    assert line["checks"]["tokens_compared"]["value"] >= 10
    if kind == "open":
        assert len(run.ttft_s) == run.attempted
        assert max(run.late_s) < 1.0
    assert 0 < run.kv_in_use < 1


@pytest.mark.parametrize("kind,number", [("closed", "mismatch_share"),
                                         ("decode", "max_logit_gap")])
def test_control_fails_the_comparison(kind, number):
    """The reference in float8, put in the program's place, reads well
    above the bf16 program on the same requests, and above the limit
    of the number the cell compares."""
    cell = _cell(kind)
    run = _run(kind)
    ctx = run.ctx
    prog = check.gap_numbers(check.gaps(
        ctx["reference"], ctx["picked"], ctx["length"]))
    ctrl = check.gap_numbers(check.gaps(
        ctx["reference"], ctx["picked"], ctx["length"], control=True))
    lim = cell.limits["checks"][number]["max"]
    assert prog[number] <= lim < ctrl[number]
    ok, _ = check.verdict(dict(ctrl, tokens_compared=999),
                          cell.limits["checks"])
    assert not ok


@pytest.mark.parametrize("kind", ["open", "decode"])
def test_altered_token_is_caught(monkeypatch, kind):
    orig = engine_mod.ServingEngine._exec_decode

    def altered(self, r):
        orig(self, r)
        r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab

    monkeypatch.setattr(engine_mod.ServingEngine, "_exec_decode", altered)
    run = _run(kind)
    assert run.correct is False
    assert run.checks["max_logit_gap"]["value"] > \
        run.checks["max_logit_gap"]["limit"]


@pytest.mark.parametrize("kind", ["closed", "decode"])
def test_step_that_leaves_its_state_unchanged_is_caught(monkeypatch, kind):
    orig = engine_mod._decode_step

    def stale(params, cfg, tok, cache, pos):
        logits, _ = orig(params, cfg, tok, cache, pos)
        return logits, cache

    monkeypatch.setattr(engine_mod, "_decode_step", stale)
    run = _run(kind)
    assert run.correct is False


def test_writing_the_trace_out_is_no_window_time(monkeypatch):
    """The profiler's stop, opened 0.5 s into the window and closed 0.5 s
    later, is left out of the window: the steps go on for the rest of
    its 2 s after a stop that takes 1.5 s."""
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: time.sleep(1.5))
    monkeypatch.setattr(harness, "_trace_context",
                        lambda run, tracer, *a: shutil.rmtree(tracer.dir))
    run = harness.run_cell(_cell("closed"), SEED, 2.0, True,
                           time.perf_counter())
    assert 2.0 <= run.window_s < 2.5
    assert sum(1 for t, _ in run.backlog if t > 1.0) >= 5


def test_percentile_interpolates_and_is_nan_when_empty():
    assert harness.percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert np.isnan(harness.percentile([], 90))
