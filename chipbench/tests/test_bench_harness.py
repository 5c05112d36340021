"""The harness end to end at smoke width on the CPU, through its
functions (the CLI itself refuses to run without a TPU): both loops,
the correctness check, the control, and planted faults."""

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
    return smoke.cell("mixtral8x7b-l2-mixed-closed", smoke.mixtral_config(),
                      smoke.closed_mix(), smoke.MOE_LIMITS)


def _run(kind, seconds=2.0):
    import time
    return harness.run_cell(_cell(kind), SEED, seconds, False,
                            time.perf_counter())


@pytest.mark.parametrize("kind", ["open", "closed"])
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


def test_control_fails_the_comparison():
    """The reference in float8, put in the program's place, reads a
    mismatch share well above the bf16 program's on the same requests,
    and above the limit."""
    run = _run("closed")
    ctx = run.ctx
    prog = check.gap_numbers(check.gaps(
        ctx["reference"], ctx["picked"], ctx["length"]))
    ctrl = check.gap_numbers(check.gaps(
        ctx["reference"], ctx["picked"], ctx["length"], control=True))
    lim = smoke.MOE_LIMITS["checks"]["mismatch_share"]["max"]
    assert prog["mismatch_share"] <= lim < ctrl["mismatch_share"]
    ok, _ = check.verdict(dict(ctrl, tokens_compared=99),
                          smoke.MOE_LIMITS["checks"])
    assert not ok


def test_altered_token_is_caught(monkeypatch):
    orig = engine_mod.ServingEngine._exec_decode

    def altered(self, r):
        orig(self, r)
        r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab

    monkeypatch.setattr(engine_mod.ServingEngine, "_exec_decode", altered)
    run = _run("open")
    assert run.correct is False
    assert run.checks["max_logit_gap"]["value"] > \
        run.checks["max_logit_gap"]["limit"]


def test_step_that_leaves_its_state_unchanged_is_caught(monkeypatch):
    orig = engine_mod._decode_step

    def stale(params, cfg, tok, cache, pos):
        logits, _ = orig(params, cfg, tok, cache, pos)
        return logits, cache

    monkeypatch.setattr(engine_mod, "_decode_step", stale)
    run = _run("closed")
    assert run.correct is False


def test_percentile_interpolates_and_is_nan_when_empty():
    assert harness.percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert np.isnan(harness.percentile([], 90))
