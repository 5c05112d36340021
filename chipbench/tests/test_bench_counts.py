"""The work a served call needs, against numbers worked out by hand."""

import json

import pytest

from chipbench import counts, spec


def _shape(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return counts.shape_of(json.load(f))


def test_qwen_call_by_hand():
    s = _shape("qwen1.5-0.5b")
    # per layer: q and o 1024x1024 each, k and v 1024x1024 each (16 kv
    # heads of 64), q/k/v bias 3x1024; MLP 3 x 1024 x 2816
    assert s.attn_params == 4 * 1024 * 1024 + 3 * 1024 == 4_197_376
    assert s.ffn_active_params == 3 * 1024 * 2816 == 8_650_752
    matmul = 24 * (4_197_376 + 8_650_752) + 151_936 * 1024
    assert matmul == 463_937_536
    # attention at pos 0: 24 layers x (QK + PV) x 16 heads x 64 x 1 key
    assert counts.call_flops(s, 0) == 2 * matmul + 24 * 4 * 16 * 64
    # bytes: weights + 49 norms of 1024 + one embedding row, in bf16;
    # cache 24 x (k, v) x 16 x 64 x 2 B = 98,304 B per token, read for
    # one position and written for one
    assert s.kv_bytes_per_token == 98_304
    assert counts.call_bytes(s, 0) == (matmul + 49 * 1024 + 1024) * 2 + \
        2 * 98_304
    assert counts.call_bytes(s, 99) - counts.call_bytes(s, 0) == 99 * 98_304


def test_mixtral_bytes_count_top_k_experts_only():
    s = _shape("mixtral-8x7b-l2")
    expert = 3 * 4096 * 14336
    assert s.ffn_active_params == 4096 * 8 + 2 * expert
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert s.attn_params == attn == 41_943_040
    matmul = 2 * (attn + 4096 * 8 + 2 * expert) + 32000 * 4096
    assert matmul == 919_666_688
    assert counts.call_bytes(s, 0) == pytest.approx(
        (matmul + 5 * 4096 + 4096) * 2 + 2 * s.kv_bytes_per_token)
    # all 8 experts would be 6 more experts per layer: not counted
    # (6.07 GB, what the program's capacity-padded einsum over all E
    # reads, against the 1.84 GB the call needs)
    all_experts = counts.call_bytes(s, 0) + 2 * 6 * expert * 2
    assert counts.call_bytes(s, 0) == 1_839_398_912
    assert all_experts == 6_067_257_344


def test_least_time_takes_the_binding_bound():
    s = _shape("mixtral-8x7b-l2")
    peaks = counts.peaks_for("TPU v5 lite")
    t = counts.least_time(s, 500, peaks)
    assert t == pytest.approx(counts.call_bytes(s, 500) / 819e9)
    assert t > counts.call_flops(s, 500) / 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")


def test_bias_is_read_from_the_configuration():
    """A family's bias is data: the same shapes without ``qkv_bias``
    count no bias, whatever the ``model_type``."""
    with open(spec.BENCH_DIR / "configs" / "qwen1.5-0.5b.json") as f:
        c = json.load(f)
    other = dict(c, model_type="llama", qkv_bias=False)
    assert counts.shape_of(c).attn_params - \
        counts.shape_of(other).attn_params == 3 * 1024
