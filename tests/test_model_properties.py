"""Hypothesis property tests on the model zoo's core invariant:
autoregressive decode with a cache reproduces the full forward pass,
across randomly drawn architectures (family, widths, patterns); and
what one decode call changes in the cache."""

import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not available in the pinned toolchain")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.models import transformer as T
from repro.models.attention import GQA, MLA
from repro.models.common import ModelConfig
from repro.models.ssm import Mamba


@st.composite
def config_strategy(draw):
    family = draw(st.sampled_from(["dense", "swa", "moe", "mla",
                                   "hybrid", "xlstm"]))
    n_heads = draw(st.sampled_from([2, 4]))
    kv = draw(st.sampled_from([1, 2])) if family != "mla" else n_heads
    kv = min(kv, n_heads)
    hd = draw(st.sampled_from([8, 16]))
    d = n_heads * hd
    kw = dict(name=f"h-{family}", n_layers=draw(st.sampled_from([2, 3])),
              d_model=d, n_heads=n_heads, n_kv_heads=kv, head_dim=hd,
              d_ff=2 * d, vocab=64, dtype="float32",
              qkv_bias=draw(st.booleans()))
    if family == "swa":
        kw["sliding_window"] = draw(st.sampled_from([4, 6]))
    elif family == "moe":
        # capacity_factor high enough that no token is ever dropped:
        # capacity-based MoE only matches decode-vs-forward when both
        # paths route without drops (a known train/serve divergence).
        kw.update(n_experts=4, top_k=2, moe_d_ff=d,
                  n_shared_experts=draw(st.sampled_from([0, 1])),
                  capacity_factor=4.0)
    elif family == "mla":
        kw.update(attn_type="mla", kv_lora_rank=d // 2,
                  q_lora_rank=draw(st.sampled_from([0, d // 2])),
                  qk_nope_head_dim=hd, qk_rope_head_dim=8, v_head_dim=hd)
    elif family == "hybrid":
        kw.update(block_pattern=("mamba", "attn"), mamba_d_state=8,
                  n_layers=2)
    elif family == "xlstm":
        kw.update(block_pattern=("slstm", "mlstm"), d_ff=0, n_layers=2,
                  n_kv_heads=n_heads)
    return ModelConfig(**kw)


@given(config_strategy(), st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=12, deadline=None)
def test_decode_matches_forward(cfg, seed):
    key = jax.random.PRNGKey(seed)
    params = T.init(key, cfg)
    B, S = 2, 9
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    logits, _ = T.forward(params, cfg, toks)
    cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
    outs = []
    for s in range(S):
        lg, cache = T.decode_step(params, cfg, toks[:, s], cache, s)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.max(jnp.abs(logits))) + 1e-6
    np.testing.assert_allclose(np.asarray(dec) / scale,
                               np.asarray(logits) / scale,
                               rtol=0, atol=3e-4)


@given(config_strategy(), st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_unrolled_decode_matches_scanned(cfg, seed):
    """decode_step(unroll=True) (serving path) == scanned decode."""
    key = jax.random.PRNGKey(seed)
    params = T.init(key, cfg)
    B = 2
    cache1 = T.init_cache(cfg, B, 4, dtype=jnp.float32)
    cache2 = T.init_cache(cfg, B, 4, dtype=jnp.float32)
    tok = jax.random.randint(key, (B,), 0, cfg.vocab)
    l1, _ = T.decode_step(params, cfg, tok, cache1, 0, unroll=False)
    l2, _ = T.decode_step(params, cfg, tok, cache2, 0, unroll=True)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)


_SMALL = dict(n_layers=3, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
              d_ff=64, vocab=64, dtype="float32")
_ROW_CASES = {
    "gqa": ModelConfig(name="r-gqa", qkv_bias=True, **_SMALL),
    "swa": ModelConfig(name="r-swa", sliding_window=3, **_SMALL),
    "mla": ModelConfig(name="r-mla", attn_type="mla", first_dense_layers=1,
                       kv_lora_rank=16, q_lora_rank=16, qk_nope_head_dim=8,
                       qk_rope_head_dim=8, v_head_dim=8,
                       **{**_SMALL, "n_kv_heads": 4}),
    "hybrid": ModelConfig(name="r-hybrid", block_pattern=("mamba", "attn"),
                          mamba_d_state=8, **{**_SMALL, "n_layers": 4}),
}


def _layer_caches(cfg, cache):
    """Each layer's cache in layer order, sliced out of the stack."""
    prefix, period = T.unit_period(cfg)
    layers = list(cache["prefix"])
    for r in range((cfg.n_layers - prefix) // period):
        layers += [jax.tree.map(lambda a: a[r], cache["stack"][u])
                   for u in range(period)]
    return layers


@pytest.mark.parametrize("case", sorted(_ROW_CASES))
def test_decode_call_writes_one_row_per_attention_layer(case, monkeypatch):
    """Each call returns every attention layer's cache as it came in but
    for row ``pos % slots``, which holds the call's K/V (``c_kv`` and
    ``k_rope`` for MLA); a recurrent layer's state is replaced.  Eight
    calls, so a window of 3 slots wraps twice."""
    cfg = _ROW_CASES[case]
    params = jax.jit(T.init, static_argnums=(1,))(jax.random.PRNGKey(0), cfg)
    B, max_len = 2, 8
    # A cache of noise, so a row left unwritten or written twice shows.
    rng = np.random.default_rng(1)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        T.init_cache(cfg, B, max_len, dtype=jnp.float32))
    toks = rng.integers(0, cfg.vocab, (B, max_len), dtype=np.int32)
    step = jax.jit(T.decode_step, static_argnums=(1,))

    # What each mixer's decode returns, layer by layer, from an
    # unrolled call on the same input.
    returned = []
    for cls in (GQA, MLA, Mamba):
        def record(*args, _decode=cls.decode):
            y, out = _decode(*args)
            returned.append(out)
            return y, out
        monkeypatch.setattr(cls, "decode", staticmethod(record))

    @jax.jit
    def mixer_outs(tok, cache, pos):
        returned.clear()
        T.decode_step(params, cfg, tok, cache, pos, unroll=True)
        return list(returned)

    for pos in range(max_len):
        _, new = step(params, cfg, toks[:, pos], cache, pos)
        outs = mixer_outs(toks[:, pos], cache, pos)
        assert len(outs) == cfg.n_layers
        for i, (old_c, new_c, out) in enumerate(zip(
                _layer_caches(cfg, cache), _layer_caches(cfg, new), outs)):
            for key in old_c:
                old_a, new_a = np.asarray(old_c[key]), np.asarray(new_c[key])
                got = np.asarray(out[key])
                if cfg.layer_kind(i) != "attn":
                    assert not np.array_equal(new_a, old_a), (i, key)
                    np.testing.assert_allclose(new_a, got, rtol=1e-5,
                                               atol=1e-5)
                    continue
                slot = pos % old_a.shape[1]
                assert got.shape == (B, 1) + old_a.shape[2:], (i, key)
                rest = np.arange(old_a.shape[1]) != slot
                np.testing.assert_array_equal(new_a[:, rest], old_a[:, rest])
                np.testing.assert_allclose(new_a[:, slot], got[:, 0],
                                           rtol=1e-5, atol=1e-5)
                assert not np.allclose(new_a[:, slot], old_a[:, slot]), (
                    i, key)
        cache = new
