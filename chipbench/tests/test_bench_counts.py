"""The work a served call needs, against numbers worked out by hand and
against the values the counts gave before each family counted its own
calls."""

import json

import pytest

from chipbench import check, counts, spec
from chipbench.reference.common import gqa_work


def _family(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        c = json.load(f)
    return c, check.family(c)


def _flops(c, fam, pos):
    return counts.call_flops(fam.work(c), pos)


def _bytes(c, fam, pos):
    return counts.call_bytes(fam.work(c), pos)


def test_qwen_call_by_hand():
    c, fam = _family("qwen1.5-0.5b")
    # per layer: q and o 1024x1024 each, k and v 1024x1024 each (16 kv
    # heads of 64), q/k/v bias 3x1024; MLP 3 x 1024 x 2816
    assert gqa_work(c, 0).params == 4 * 1024 * 1024 + 3 * 1024 == 4_197_376
    assert counts.swiglu_params(1024, 2816) == 3 * 1024 * 2816 == 8_650_752
    matmul = 24 * (4_197_376 + 8_650_752) + 151_936 * 1024
    assert fam.work(c).multiplied == matmul == 463_937_536
    # attention at pos 0: 24 layers x (QK + PV) x 16 heads x 64 x 1 key
    assert _flops(c, fam, 0) == 2 * matmul + 24 * 4 * 16 * 64
    # bytes: weights + 49 norms of 1024 + one embedding row, in bf16;
    # cache 24 x (k, v) x 16 x 64 x 2 B = 98,304 B per token, read for
    # one position and written for one
    assert fam.work(c).cache_bytes_per_token == 98_304
    assert _bytes(c, fam, 0) == (matmul + 49 * 1024 + 1024) * 2 + \
        2 * 98_304
    assert _bytes(c, fam, 99) - _bytes(c, fam, 0) == 99 * 98_304


def test_mixtral_bytes_count_top_k_experts_only():
    c, fam = _family("mixtral-8x7b-l2")
    expert = 3 * 4096 * 14336
    assert counts.routed_params(4096, 8, 2, 14336) == 4096 * 8 + 2 * expert
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert gqa_work(c, 0).params == attn == 41_943_040
    matmul = 2 * (attn + 4096 * 8 + 2 * expert) + 32000 * 4096
    assert matmul == 919_666_688
    assert _bytes(c, fam, 0) == pytest.approx(
        (matmul + 5 * 4096 + 4096) * 2 +
        2 * fam.work(c).cache_bytes_per_token)
    # all 8 experts would be 6 more experts per layer: not counted
    # (6.07 GB, what the program's capacity-padded einsum over all E
    # reads, against the 1.84 GB the call needs)
    all_experts = _bytes(c, fam, 0) + 2 * 6 * expert * 2
    assert _bytes(c, fam, 0) == 1_839_398_912
    assert all_experts == 6_067_257_344


def test_least_time_takes_the_binding_bound():
    c, fam = _family("mixtral-8x7b-l2")
    peaks = counts.peaks_for("TPU v5 lite")
    t = counts.least_time(fam.work(c), 500, peaks)
    assert t == pytest.approx(_bytes(c, fam, 500) / 819e9)
    assert t > _flops(c, fam, 500) / 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")


def test_bias_is_read_from_the_configuration():
    """A family's bias is data: the same shapes without ``qkv_bias``
    count no bias, whatever the ``model_type``."""
    c, fam = _family("qwen1.5-0.5b")
    other = dict(c, model_type="llama", qkv_bias=False)
    assert gqa_work(c, 0).params - gqa_work(other, 0).params == 3 * 1024
    assert fam.work(c).multiplied - fam.work(other).multiplied == \
        24 * 3 * 1024


#: the calls' FLOPs and bytes as ``counts.shape_of`` counted them before
#: each family counted its own calls (the parent's code, run on the CPU)
PARENT = {
    "qwen1.5-0.5b": {
        "flops": {0: 927973376.0, 1: 928071680.0, 255: 953040896.0,
                  1151: 1041121280.0, 1279: 1053704192.0},
        "bytes": {0: 928174080.0, 1: 928272384.0, 255: 953241600.0,
                  1151: 1041321984.0, 1279: 1053904896.0}},
    "mixtral-8x7b-l2": {
        "flops": {0: 1839366144.0, 1: 1839398912.0, 255: 1847721984.0,
                  1151: 1877082112.0, 1279: 1881276416.0},
        "bytes": {0: 1839398912.0, 1: 1839407104.0, 255: 1841487872.0,
                  1151: 1848827904.0, 1279: 1849876480.0}},
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_counts_are_the_parents_bit_for_bit(name):
    c, fam = _family(name)
    for pos, want in PARENT[name]["flops"].items():
        assert _flops(c, fam, pos) == want, pos
    for pos, want in PARENT[name]["bytes"].items():
        assert _bytes(c, fam, pos) == want, pos
