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


def _prims(cfg, p, x) -> list[str]:
    """Primitives of the local MoE's top-level jaxpr."""
    jaxpr = jax.make_jaxpr(lambda p, x: MoE._fwd_local(p, cfg, x))(p, x)
    return [e.primitive.name for e in jaxpr.jaxpr.eqns]


def _share_cfg(offset: int) -> ModelConfig:
    """A router over 16 experts in 4 groups of 4, top-3 from the best 2
    groups, holding the group at ``offset``; gates as DeepSeek-V2's."""
    return _cfg(1, "float32").replace(
        n_experts=16, top_k=3, experts_held=4, expert_offset=offset,
        n_group=4, topk_group=2, norm_topk_prob=False,
        routed_scaling_factor=16.0)


def test_whole_bank_gathered_path_has_no_branch():
    cfg = _cfg(1, "float32")
    assert not cfg.expert_share
    p = MoE.init(jax.random.PRNGKey(0), cfg)
    assert "cond" not in _prims(cfg, p, jnp.ones((1, 1, cfg.d_model)))


@pytest.mark.parametrize("offset", [0, 12])
def test_held_share_branches_each_assignment(offset, monkeypatch):
    """A one-token call of a held share runs each of its K assignments
    in a branch that reads an expert only where it is held, and gives
    what the grouped buffer of the held experts gives."""
    cfg = _share_cfg(offset)
    p = MoE.init(jax.random.PRNGKey(1), cfg)
    assert p["experts"]["w_gate"].shape == (4, cfg.d_model, cfg.moe_d_ff)
    assert p["router"]["w"].shape == (cfg.d_model, 16)
    # a token drawn to the held group's first expert, so that it is
    # routed there and to others, held or not
    x = 4.0 * p["router"]["w"][None, None, :, offset] + jax.random.normal(
        jax.random.PRNGKey(offset), (1, 1, cfg.d_model))
    assert MoE.local_path(cfg, 1) == "gathered"
    assert _prims(cfg, p, x).count("cond") == cfg.top_k

    fwd = jax.jit(MoE._fwd_local, static_argnums=1)
    y, _ = fwd(p, cfg, x)
    without = MoE._shared_tp(p, cfg, x[0], None)
    assert float(jnp.abs(y[0] - without).max()) > 0.1     # a held expert ran
    monkeypatch.setattr(MoE, "local_path",
                        staticmethod(lambda cfg, n: "grouped"))
    y_ref, _ = jax.jit(MoE._fwd_local, static_argnums=1)(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=0,
                               atol=1e-6 * float(np.abs(y_ref).max()))


def test_held_share_refuses_a_mesh_path():
    cfg = _share_cfg(0)
    for path in (MoE._fwd_ep, MoE._fwd_tp):
        with pytest.raises(NotImplementedError, match="holding 4 of 16"):
            path({}, cfg, jnp.ones((1, 1, cfg.d_model)))
