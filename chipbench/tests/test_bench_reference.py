"""The float32 references against ``repro.models.transformer`` at smoke
widths, with the program computing in float32 too: full forward, and
prefill then decode through the program's own cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, program
from chipbench.tests import smoke
from repro.models import transformer as T

CONFIGS = {"qwen2": smoke.qwen_config, "mixtral": smoke.mixtral_config}


def _setup(family, seed=3):
    c = CONFIGS[family]()
    cfg = program.model_config(c, family).replace(dtype="float32",
                                                  capacity_factor=8.0)
    params = program.make_params(cfg, seed, dtype=jnp.float32)
    ref = check.reference_for(c, program.reference_weights(params, cfg, c))
    ids = np.random.default_rng(seed).integers(0, cfg.vocab, 24)
    return c, cfg, params, ref, ids


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_reference_matches_program_forward(family):
    c, cfg, params, ref, ids = _setup(family)
    want = np.asarray(ref.logits(ids))
    got, _ = T.forward(params, cfg, jnp.asarray(ids, jnp.int32)[None],
                       remat=False)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_reference_matches_prefill_then_decode(family):
    c, cfg, params, ref, ids = _setup(family, seed=4)
    want = np.asarray(ref.logits(ids))
    cache = T.init_cache(cfg, 1, 32, dtype=jnp.float32)
    step = jax.jit(T.decode_step, static_argnums=(1,))
    for s, t in enumerate(ids):
        got, cache = step(params, cfg, jnp.asarray([t], jnp.int32), cache, s)
        np.testing.assert_allclose(np.asarray(got[0]), want[s], atol=2e-4,
                                   rtol=2e-4)


def test_reference_sees_qkv_bias_and_router():
    """Perturbing a bias (qwen2) or the router (mixtral) moves the
    reference's logits: those parts are really computed."""
    for family, key in (("qwen2", "bq"), ("mixtral", "router")):
        c, cfg, params, ref, ids = _setup(family)
        base = np.asarray(ref.logits(ids))
        w = program.reference_weights(params, cfg, c)
        w["layers"][key] = w["layers"][key] * 3.0 + 0.5
        moved = np.asarray(check.reference_for(c, w).logits(ids))
        assert np.max(np.abs(moved - base)) > 1e-2, family


def test_control_rounds_weights_through_float8():
    c, cfg, params, ref, ids = _setup("mixtral")
    a = np.asarray(ref.logits(ids))
    b = np.asarray(ref.logits(ids, control=True))
    diff = np.max(np.abs(a - b))
    assert 1e-3 < diff < 0.5 * np.max(np.abs(a))
