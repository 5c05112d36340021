"""The benchmark's side of the system under test.

The program is :mod:`repro`: its ``ModelConfig``, the parameter layout
that ``repro.models.transformer.init`` defines, and
``repro.serve.ServingEngine``. This module maps a configuration file
(the published ``config.json`` keys, as run) onto them, makes the
weights from the seed, and gives the reference a plain view of those
weights. Nothing here computes a result the check compares.

What differs between families lives in files of their own, found by
the configuration's ``model_type``: a family adds its configuration
(``chipbench/configs/<name>.json``), its program mapping
(``chipbench/families/<model_type>.py``: ``model_config`` and
``reference_weights``), and its reference with the work a call needs
(``chipbench/reference/<model_type>.py``: ``reference`` and
``work``). This module holds what is true of the
program for every family.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.serve import ServingEngine, SchedulerPolicy

from . import spec

__all__ = ["PROGRAM_RMS_EPS", "family", "model_config", "make_params",
           "build_engine", "reference_weights"]

#: the program's RMSNorm eps (``repro.models.common.rmsnorm``); it has
#: no option for another, so a configuration must state this one
PROGRAM_RMS_EPS = 1e-6


def family(c: dict, bench_dir=spec.BENCH_DIR):
    """The program mapping of the configuration's family,
    ``<bench_dir>/families/<model_type>.py``."""
    return spec.load_family("families", c, bench_dir)


def model_config(c: dict, name: str, bench_dir=spec.BENCH_DIR
                 ) -> ModelConfig:
    """The program's ``ModelConfig`` for configuration file ``c``, as
    its family maps it; a configuration that asks for what the program
    cannot compute is an error."""
    if c["hidden_act"] != "silu":
        raise ValueError(f"{name}: the program's MLP is SwiGLU (silu)")
    if c["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"{name}: rms_norm_eps {c['rms_norm_eps']} but the "
                         f"program's RMSNorm is fixed at {PROGRAM_RMS_EPS}")
    if c.get("attention_bias"):
        raise ValueError(f"{name}: the program has no bias on the attention "
                         f"output projection")
    return family(c, bench_dir).model_config(c, name)


def _leaf_scale(names: list[str], shape: tuple, n_layers: int) -> tuple:
    """(mean, std) of one parameter, by its place in the tree."""
    last = names[-1]
    if last == "scale":                       # RMSNorm gains
        return 1.0, 0.1
    if last == "b":                           # q/k/v biases
        return 0.0, 0.1
    if names[0] == "embed":
        return 0.0, 0.02
    std = 1.0 / math.sqrt(shape[-2])          # fan-in
    if "wo" in names or "w_down" in names:    # projections back into the
        std /= math.sqrt(2 * n_layers)        # residual stream
    return 0.0, std


def make_params(cfg: ModelConfig, seed: int, dtype=jnp.bfloat16):
    """The program's parameter tree, random from ``seed`` (any whole
    number up to 2**63), in ``dtype``, made on the device in one jitted
    call."""
    shapes = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            names = [getattr(p, "key", None) for p in path]
            names = [n for n in names if isinstance(n, str)]
            mean, std = _leaf_scale(names, sd.shape, cfg.n_layers)
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            out.append((mean + std * z).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return jax.jit(gen)(key)


def build_engine(cfg: ModelConfig, params, max_len: int) -> ServingEngine:
    """The engine as a deployment runs it: the default policy."""
    return ServingEngine(cfg, params, max_len=max_len,
                         policy=SchedulerPolicy())


def reference_weights(params, cfg: ModelConfig, c: dict,
                      bench_dir=spec.BENCH_DIR) -> dict:
    """The plain view of the program's weights that the family's
    reference reads (``chipbench/families/common.py``)."""
    return family(c, bench_dir).reference_weights(params, cfg)
