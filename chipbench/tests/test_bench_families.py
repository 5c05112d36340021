"""A model family is new files only: its program mapping
(``families/<model_type>.py``) and its reference with its counts
(``reference/<model_type>.py``), found by the configuration's
``model_type``; the shared files name no family's keys."""

import json
import re
import shutil
import time
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, counts, harness, program, spec, trace_reduce
from chipbench.tests import smoke
from repro.models import transformer as T
from repro.models.common import ModelConfig


def _config(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


#: the ``ModelConfig`` each configuration mapped to before the mapping
#: moved into its family's file (the parent's ``program.model_config``)
PARENT_CONFIGS = {
    "qwen1.5-0.5b": ModelConfig(
        name="qwen1.5-0.5b", n_layers=24, d_model=1024, n_heads=16,
        n_kv_heads=16, head_dim=64, d_ff=2816, vocab=151936, qkv_bias=True,
        sliding_window=None, rope_theta=1000000.0, n_experts=0, top_k=0,
        moe_d_ff=0, tie_embeddings=True, dtype="bfloat16"),
    "mixtral-8x7b-l2": ModelConfig(
        name="mixtral-8x7b-l2", n_layers=2, d_model=4096, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=0, vocab=32000, qkv_bias=False,
        sliding_window=None, rope_theta=1000000.0, n_experts=8, top_k=2,
        moe_d_ff=14336, tie_embeddings=False, dtype="bfloat16"),
}


@pytest.mark.parametrize("name", sorted(PARENT_CONFIGS))
def test_family_maps_the_parents_model_config(name):
    c = _config(name)
    assert program.family(c).model_config(c, name) == PARENT_CONFIGS[name]
    assert program.model_config(c, name) == PARENT_CONFIGS[name]


#: every per-layer and end-to-end reading of the two cells from the
#: recorded context of :func:`_recorded_ctx`, by the parent's readers
PARENT_READINGS = {
    "qwen05b-chat-open": {
        "compose_ms.itl": 0.0775,
        "exec_ms_per_token.ttft": 4.0694519804666305,
        "decode_step_roofline.ttft": 47.88428489357149,
        "idle_share.ttft": 18.970369144181443,
        "mfu.ttft": 0.15340051946487496,
        "ttft_p90_s": 0.19, "itl_p95_s": 0.0295, "setup_s": 12.5},
    "mixtral8x7b-l2-mixed-closed": {
        "compose_ms.tps": 0.0775,
        "exec_ms_per_token.tps": 4.0694519804666305,
        "decode_step_roofline.tps": 89.15257311028033,
        "idle_share.tps": 18.970369144181443,
        "mfu.tps": 0.28808177337096674,
        "output_tokens_per_s": 24.68, "setup_s": 12.5},
}


def _recorded_ctx(cell):
    """A traced run's context as the harness builds it: a reduced trace
    of 1,843 decode calls, their positions, the engine's counters."""
    summ = trace_reduce.TraceSummary(
        window_s=6.041, busy_s=4.895, n_devices=1,
        programs={"jit_decode_step": 4.656},
        program_calls={"jit_decode_step": 1843})
    run = NS(tokens=1234, window_s=50.0, setup_s=12.5,
             itl_s=[0.02, 0.03], ttft_s=[0.1, 0.2])
    run.ctx = {"run": run, "trace": summ,
               "positions": [(37 * i) % 1280 for i in range(1843)],
               "counters": {"engine_steps": 400, "phase_compose_s": 0.031,
                            "phase_execute_s": 7.5},
               "work": harness.call_work(cell),
               "peaks": counts.peaks_for("TPU v5 lite")}
    return run


@pytest.mark.parametrize("name", sorted(PARENT_READINGS))
def test_readers_read_the_parents_values(name):
    cell = spec.load_cell(name)
    run = _recorded_ctx(cell)
    got = harness.read_metrics(cell.per_layer + cell.end_to_end, run)
    assert {k: v["value"] for k, v in got.items()} == PARENT_READINGS[name]


_GENERIC = ("harness.py", "program.py", "counts.py", "spec.py", "check.py")
#: keys the program states for every family (its RMSNorm eps, SwiGLU, no
#: output-projection bias) and the key that finds the family
_PROGRAM_FACTS = {"hidden_act", "rms_norm_eps", "attention_bias",
                  "model_type"}


def test_generic_files_name_no_family_key():
    keys = set()
    for kind in spec.FAMILY_KINDS:
        for path in (spec.BENCH_DIR / kind).glob("*.py"):
            keys |= set(re.findall(
                r'\b(?:c|cfg|config)(?:\[|\.get\()"(\w+)"', path.read_text()))
    keys -= _PROGRAM_FACTS
    assert {"num_local_experts", "qkv_bias", "intermediate_size"} <= keys
    for name in _GENERIC:
        text = (spec.BENCH_DIR / name).read_text()
        named = {k for k in keys if f'"{k}"' in text or f"'{k}'" in text}
        assert not named, (name, named)


def _bench_copy(tmp_path):
    """A copy of ``BENCHMARK.json`` and ``chipbench/`` (no caches, no
    recorded trace), and the bytes of every file of the copied
    ``chipbench/`` (``BENCHMARK.json`` gains entries)."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    bdir = tmp_path / "chipbench"
    shutil.copytree(spec.BENCH_DIR, bdir, ignore=shutil.ignore_patterns(
        ".jax_cache", "__pycache__", "testdata"))
    return bdir, {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}


@pytest.mark.parametrize("missing", spec.FAMILY_KINDS)
def test_a_family_without_its_file_fails_at_cell_load(tmp_path, missing):
    bdir, _ = _bench_copy(tmp_path)
    c = dict(smoke.qwen_config(), model_type="nofamily")
    (bdir / "configs" / "nofamily.json").write_text(json.dumps(c))
    for kind in spec.FAMILY_KINDS:
        if kind != missing:
            shutil.copy(bdir / kind / "qwen2.py", bdir / kind / "nofamily.py")
    bench = spec.load_benchmark(tmp_path)
    bench["configs"].append({"name": "nofamily", "source": "test",
                             "file": "chipbench/configs/nofamily.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "nofamily-chat",
                               "config": "nofamily",
                               "traffic": "short-chat-open", "chips": 1,
                               "why": "test"})
    shutil.copy(bdir / "limits" / "qwen05b-chat-open.json",
                bdir / "limits" / "nofamily-chat.json")
    want = str(bdir / missing / "nofamily.py")
    with pytest.raises(FileNotFoundError, match=re.escape(want)):
        spec.load_cell("nofamily-chat", root=tmp_path, bench=bench,
                       bench_dir=bdir)


@pytest.fixture
def toy_moe(tmp_path):
    """The toy_moe family, a cell, and two per-layer entries added as
    new files and entries to a copy of the benchmark."""
    bdir, before = _bench_copy(tmp_path)
    (bdir / "configs" / "toy-moe.json").write_text(
        json.dumps(smoke.toy_moe_config()))
    (bdir / "families" / "toy_moe.py").write_text(smoke.TOY_MOE_FAMILY)
    (bdir / "reference" / "toy_moe.py").write_text(smoke.TOY_MOE_REFERENCE)
    (bdir / "traffic" / "toy-closed.json").write_text(
        json.dumps(smoke.closed_mix()))
    (bdir / "limits" / "toy-moe-closed.json").write_text(
        json.dumps(smoke.MOE_LIMITS))
    bench = spec.load_benchmark(tmp_path)
    bench["configs"].append({"name": "toy-moe", "source": "test",
                             "file": "chipbench/configs/toy-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-moe-closed", "config": "toy-moe",
                               "traffic": "toy-closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "output_tokens_per_s":
            m["workloads"].append("toy-moe-closed")
    for name in ("decode_step_roofline", "mfu"):
        bench["per_layer"].append({
            "name": f"{name}.toy", "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "test",
            "moves": "output_tokens_per_s", "workloads": ["toy-moe-closed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("toy-moe-closed", root=tmp_path, bench_dir=bdir)
    return cell, before


def test_toy_moe_family_needs_only_new_files(toy_moe):
    """A family with a leading dense layer, a shared expert and
    DeepSeek-V2's mixture-of-experts key names runs through the harness
    with ``correct`` true, and its calls are counted by its own file."""
    cell, before = toy_moe
    run = harness.run_cell(cell, 2 ** 33 + 15, 2.0, False, time.perf_counter())
    assert run.correct, run.checks
    w = run.ctx["reference"].w
    assert jax.tree.leaves(w["prefix"])[0].shape[0] == 1
    assert jax.tree.leaves(w["layers"])[0].shape[0] == 2
    assert {"shared_gate", "experts_gate", "router"} <= set(w["layers"])

    # a recorded context: the CPU has no device trace
    run.ctx.update(
        trace=trace_reduce.TraceSummary(
            window_s=2.0, busy_s=1.0, n_devices=1,
            programs={"jit_decode_step": 0.5},
            program_calls={"jit_decode_step": 10}),
        positions=list(range(10)), work=harness.call_work(cell),
        peaks=counts.peaks_for("TPU v5 lite"))
    per = harness.read_metrics(cell.per_layer, run, cell.bench_dir)
    # by hand, d 64, 4 heads of 16 over 2 kv heads: attention 3 x 4,096;
    # the dense layer's MLP 3 x 64 x 128; a MoE layer's router 64 x 8,
    # two routed experts and one shared of 3 x 64 x 32; head 256 x 64
    attn, dense, moe = 3 * 4096, 3 * 64 * 128, 64 * 8 + 3 * (3 * 64 * 32)
    body = (attn + dense) + 2 * (attn + moe)
    flops = [2.0 * (body + 256 * 64) + 3 * 4 * 4 * 16 * (p + 1)
             for p in range(10)]
    nbytes = [2.0 * (body + 7 * 64 + 256 * 64 + 64) +
              3 * 2 * 2 * 16 * 2 * (p + 2) for p in range(10)]
    least = sum(max(f / 197e12, b / 819e9) for f, b in zip(flops, nbytes))
    assert per["decode_step_roofline.toy"]["value"] == \
        pytest.approx(100 * least / 0.5, rel=1e-12)
    assert per["mfu.toy"]["value"] == \
        pytest.approx(100 * sum(flops) / (2.0 * 197e12), rel=1e-12)
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_toy_moe_reference_matches_the_program(toy_moe):
    """With the program in float32 too, the family's reference gives the
    program's logits, prefill then decode through its cache: the
    mapping names every weight the program computes with."""
    cell, _ = toy_moe
    c = cell.config
    cfg = program.model_config(c, "toy", cell.bench_dir).replace(
        dtype="float32", capacity_factor=8.0)
    params = program.make_params(cfg, 6, dtype=jnp.float32)
    ref = check.reference_for(
        c, program.reference_weights(params, cfg, c, cell.bench_dir),
        cell.bench_dir)
    ids = np.random.default_rng(6).integers(0, cfg.vocab, 20)
    want = np.asarray(ref.logits(ids))
    cache = T.init_cache(cfg, 1, 32, dtype=jnp.float32)
    step = jax.jit(T.decode_step, static_argnums=(1,))
    for s, t in enumerate(ids):
        got, cache = step(params, cfg, jnp.asarray([t], jnp.int32), cache, s)
        np.testing.assert_allclose(np.asarray(got[0]), want[s], atol=2e-4,
                                   rtol=2e-4)
