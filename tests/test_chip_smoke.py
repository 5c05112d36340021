"""``chip_smoke.py``'s checks at smoke width on the CPU, its refusal to
run without a TPU, and the entry points' compile-cache setting."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models import transformer as T

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("qwen1.5-0.5b", "smoke")
    return cfg, T.init(jax.random.PRNGKey(0), cfg)


def test_serve_and_check_at_smoke_width(smoke):
    cfg, params = smoke
    out = cs.serve_and_check(cfg, params, seed=0)
    assert len(out["outputs"]) == cs.N_REQUESTS
    assert all(len(t) == cs.NEW_TOKENS for t in out["outputs"].values())
    lens = [p["prompt_len"] for p in out["prefill"]]
    assert all(cs.PROMPT_LEN[0] <= n <= cs.PROMPT_LEN[1] for n in lens)
    assert all(p["max_abs_diff"] <= p["tol"] for p in out["prefill"])


def test_compare_kernels_at_smoke_width(smoke):
    cfg, _ = smoke
    diffs = cs.compare_kernels(cfg, seed=0, interpret=True)
    assert set(diffs) == {"flash_attention", "decode_attention", "rmsnorm"}
    assert all(d <= cs.KERNEL_ATOL for d in diffs.values())


def test_serve_replicas_matches_one_replica(smoke):
    cfg, params = smoke
    dev = jax.devices()[0]
    out = cs.serve_replicas(cfg, params, seed=0, devices=[dev] * 4)
    assert out["device_ids"] == [dev.id] * 4
    assert out["served"] == [1, 1, 1, 1]


@pytest.mark.parametrize("outputs,match", [
    ({0: [1, 2]}, "got no output"),
    ({0: [1, 2], 1: [3]}, "1 tokens"),
    ({0: [1, 2], 1: [3, 512]}, "outside"),
    ({0: [1, 2], 1: [-1, 4]}, "outside"),
])
def test_check_tokens_rejects(outputs, match):
    reqs = cs.make_requests(512, seed=0, n=2, new_tokens=2)
    with pytest.raises(cs.SmokeFailure, match=match):
        cs.check_tokens(outputs, reqs, vocab=512)


def test_compare_logits_bounds():
    ref = np.array([0.0, 1.0, 4.0, 3.99])
    assert cs.compare_logits(ref + 0.1, ref)["argmax_equal"]
    # a near-tie (within the bound) may flip the argmax
    flipped = cs.compare_logits(np.array([0.0, 1.0, 3.9, 4.0]), ref)
    assert not flipped["argmax_equal"]
    with pytest.raises(cs.SmokeFailure, match="max"):
        cs.compare_logits(ref + 1.0, ref)
    with pytest.raises(cs.SmokeFailure, match="not finite"):
        cs.compare_logits(np.array([0.0, np.nan, 4.0, 3.99]), ref)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_enable_compile_cache(monkeypatch, tmp_path, env_dir):
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.enable_compile_cache()
            want = os.path.join(_ROOT, ".jax_cache")
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            path = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
            assert compile_cache.enable_compile_cache() == path
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_repo_cache_dir_is_ignored():
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
