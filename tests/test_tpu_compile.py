"""Ahead-of-time compiles for a described TPU v5e at qwen1.5-0.5b
widths: the Pallas kernels of the serving path and the full-width
decode step, which must not copy its KV cache; and one MoE layer at
mixtral-8x7b widths, whose one-token call must read only its routed
experts.  No chip is needed, and nothing runs: the TPU compiler
refuses what the chip would refuse (misaligned blocks, unsupported
primitives, programs that do not fit its memory).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import transformer as T
from repro.models.moe import MoE

_HBM_BYTES = 16 * 2 ** 30          # one v5e chip
_BF = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:   # else the compiler logs to /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def qwen():
    return get_config("qwen1.5-0.5b", "full")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, cfg, sh):
    H, Hkv, D, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    if name == "flash_attention":
        qkv = _spec((1, 512, H, D), _BF, sh), _spec((1, 512, Hkv, D), _BF, sh)
        return (lambda q, k, v: ops.flash_attention(q, k, v,
                                                    interpret=False),
                (qkv[0], qkv[1], qkv[1]))
    if name == "decode_attention":
        kv = _spec((4, 1024, Hkv, D), _BF, sh)
        return (lambda q, k, v, n: ops.decode_attention(q, k, v, n,
                                                        interpret=False),
                (_spec((4, H, D), _BF, sh), kv, kv,
                 _spec((4,), jnp.int32, sh)))
    return (lambda x, s: ops.rmsnorm(x, s, interpret=False),
            (_spec((512, d), _BF, sh), _spec((d,), jnp.float32, sh)))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "rmsnorm"])
def test_kernel_compiles_for_v5e(one_chip, qwen, name):
    fn, args = _kernel_case(name, qwen, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compiled_decode_step(sharding, cfg, max_len):
    def place(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding),
                            tree)

    params = place(jax.eval_shape(lambda k: T.init(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: T.init_cache(cfg, 1, max_len)))
    tok = _spec((1,), jnp.int32, sharding)
    pos = _spec((), jnp.int32, sharding)
    return jax.jit(T.decode_step, static_argnums=(1,)).lower(
        params, cfg, tok, cache, pos).compile()


def test_decode_step_fits_one_v5e(one_chip, qwen):
    mem = _compiled_decode_step(one_chip, qwen, 256).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < _HBM_BYTES, total


def test_decode_step_copies_no_cache_slice(one_chip, qwen):
    """A call writes one K/V row per layer: no ``copy`` whose result
    has the cache's length remains (a layer's cache passed through the
    layer scan would be copied in and out, four slices a layer)."""
    max_len = 1104
    text = _compiled_decode_step(one_chip, qwen, max_len).as_text()
    ops = re.findall(r"(%\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", text)
    long = [(name, op) for name, dims, op in ops
            if str(max_len) in dims.split(",")]
    assert long, "no instruction of the cache's length parsed"
    copies = [name for name, op in long if op == "copy"]
    assert not copies, copies


def test_one_token_moe_reads_only_the_routed_experts(one_chip, monkeypatch):
    """At T = 1 the gathered path streams the top-2 experts' weights,
    a quarter of the grouped buffer's 8; a gather that copied the
    weights first would read more than the grouped path, not less."""
    cfg = get_config("mixtral-8x7b", "full")
    p = jax.tree.map(lambda a: _spec(a.shape, _BF, one_chip),
                     jax.eval_shape(lambda k: MoE.init(k, cfg),
                                    jax.random.PRNGKey(0)))
    x = _spec((1, 1, cfg.d_model), _BF, one_chip)

    def bytes_accessed():
        fn = jax.jit(lambda p, x: MoE._fwd_local(p, cfg, x)[0])
        cost = fn.lower(p, x).compile().cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)["bytes accessed"]

    assert MoE.local_path(cfg, 1) == "gathered"
    gathered = bytes_accessed()
    monkeypatch.setattr(MoE, "local_path",
                        staticmethod(lambda cfg, n: "grouped"))
    grouped = bytes_accessed()
    routed = 3 * cfg.top_k * cfg.d_model * cfg.moe_d_ff * 2      # bf16
    assert routed <= gathered <= 0.3 * grouped, (gathered, grouped)
