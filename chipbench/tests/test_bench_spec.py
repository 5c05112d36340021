"""``BENCHMARK.json`` resolves to its files, and a new configuration,
mix and per-layer metric need only new files and new entries."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import check, harness, spec
from chipbench.tests import smoke


def test_every_name_resolves_to_its_file():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file(), c["name"]
        with open(spec.ROOT / c["file"]) as f:
            conf = json.load(f)
        for kind in spec.FAMILY_KINDS:
            assert spec.family_path(kind, conf).is_file()
        assert c["file"].startswith(bench["paths"][0] + "/")
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.limits["checks"]
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    for m in bench["end_to_end"] + bench["per_layer"]:
        path = spec.metric_reader_path(m["name"])
        assert path.is_file(), m["name"]
        assert callable(spec.load_module(path).read)


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """A configuration of a family the benchmark does not run yet (a
    Llama-style dense model: no bias, untied head), its program mapping
    and reference, a new mix and a new per-layer metric, added as files
    beside copies of the existing ones, run through the harness with no
    change to any existing file."""
    bdir = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "limits", "metrics", "reference",
                "families"):
        shutil.copytree(spec.BENCH_DIR / sub, bdir / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}

    (bdir / "configs" / "llama-tiny.json").write_text(
        json.dumps(smoke.llama_config()))
    (bdir / "families" / "llama.py").write_text(smoke.LLAMA_FAMILY)
    (bdir / "reference" / "llama.py").write_text(smoke.LLAMA_REFERENCE)
    (bdir / "traffic" / "tiny-open.json").write_text(
        json.dumps(smoke.open_mix()))
    (bdir / "limits" / "tiny-chat.json").write_text(json.dumps(smoke.LIMITS))
    (bdir / "metrics" / "tokens_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['run'].tokens)\n")
    bench["configs"].append({"name": "llama-tiny", "source": "test",
                             "file": "chipbench/configs/llama-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-chat", "config": "llama-tiny",
                               "traffic": "tiny-open", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny-chat")
    bench["per_layer"].append({"name": "tokens_in_window.tiny",
                               "unit": "tokens", "better": "higher",
                               "source": "host_clock", "layer": "harness",
                               "moves": "ttft_p90_s",
                               "workloads": ["tiny-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny-chat", root=tmp_path, bench_dir=bdir)
    run = harness.run_cell(cell, 9, 1.5, False, time.perf_counter())
    assert run.correct, run.checks
    fam = check.family(cell.config, cell.bench_dir)
    assert fam.__file__ == str(bdir / "reference" / "llama.py")
    assert "bq" not in run.ctx["reference"].w["layers"]
    assert run.ctx["reference"].w["head"] is not None
    e2e = harness.read_metrics(cell.end_to_end, run, bdir)
    assert "ttft_p90_s" in e2e and "setup_s" in e2e
    per = harness.read_metrics(cell.per_layer, run, bdir)
    assert per["tokens_in_window.tiny"]["value"] == run.tokens
    for p, data in before.items():
        assert p.read_bytes() == data, p


def _cli(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen05b-chat-open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _cli(spec.ROOT, env)
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_cli_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _cli(tmp_path, env)
    assert r.returncode != 0
    assert "{" not in r.stdout
