"""The local MoE's two paths agree: for decode-sized inputs (T·K < E)
the gathered path, which reads each routed expert by its id, gives
what the grouped (E, C, d) buffer gives, in float32 to rounding and in
bfloat16 within one ulp of the output's scale; from T·K = E on, the
grouped path runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import ModelConfig
from repro.models.moe import MoE

E, K = 8, 2


def _cfg(shared: int, dtype: str) -> ModelConfig:
    return ModelConfig(name="moe-paths", n_layers=2, d_model=64, n_heads=4,
                       n_kv_heads=4, head_dim=16, d_ff=0, vocab=32,
                       n_experts=E, top_k=K, moe_d_ff=96,
                       n_shared_experts=shared, dtype=dtype)


def _ulp(scale: float) -> float:
    """One bfloat16 ulp at ``scale`` (8 significant bits)."""
    return 2.0 ** (np.floor(np.log2(scale)) - 7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shared", [0, 1])
@pytest.mark.parametrize("n_tokens", [1, 2, 3, 4])
def test_gathered_path_matches_grouped(n_tokens, shared, dtype,
                                       monkeypatch):
    cfg = _cfg(shared, dtype)
    p = jax.tree.map(lambda a: a.astype(dtype),
                     MoE.init(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(n_tokens),
                          (1, n_tokens, cfg.d_model)).astype(dtype)
    path = MoE.local_path(cfg, n_tokens)
    assert path == ("gathered" if n_tokens * K < E else "grouped")

    y, aux = jax.jit(lambda p, x: MoE._fwd_local(p, cfg, x))(p, x)
    monkeypatch.setattr(MoE, "local_path",
                        staticmethod(lambda cfg, n: "grouped"))
    y_ref, aux_ref = jax.jit(lambda p, x: MoE._fwd_local(p, cfg, x))(p, x)

    assert y.dtype == y_ref.dtype == jnp.dtype(dtype)
    y, y_ref = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    scale = float(np.abs(y_ref).max())
    tol = 1e-6 * scale if dtype == "float32" else _ulp(scale)
    np.testing.assert_allclose(y, y_ref, rtol=0, atol=tol)
    for k in ("moe_lb_loss", "moe_z_loss"):
        assert float(aux[k]) == pytest.approx(float(aux_ref[k]), rel=1e-6)
    assert float(aux["moe_drop_frac"]) == 0.0
    assert float(aux_ref["moe_drop_frac"]) == pytest.approx(0.0, abs=1e-6)
