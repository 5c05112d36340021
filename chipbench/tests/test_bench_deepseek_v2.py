"""The DeepSeek-V2 family at a toy size on the CPU: the serving engine
against the family's float32 reference, prefill then decode; each
published mechanism shown to matter by leaving it out; the experts
held on one chip tied to the uncut layer; the work of a call at the
published widths; and the toy cell through the harness.

The toy keeps the configuration's keys and mechanisms: a leading dense
layer and 4 MoE layers, a router over 40 experts in 8 groups of 5,
routed top-4 from the best 3 groups, one group held, 2 shared experts,
YaRN with an original context of 64 so that the ramp falls on the
rope pairs (pairs 0, 1 and 2 get 0, 0.5 and 1 of the division by 40).
A group of 5 held is more than the top-4 a token takes, so a one-token
call runs the gathered path with its per-assignment branches, as the
published 20 held against top-6 do; top-4 from 3 groups is more than
the groups kept, so group limiting can change the choice.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.common as model_common
from chipbench import check, counts, harness, program, spec
from chipbench.families import common as families
from chipbench.reference import deepseek_v2 as ref_mod
from chipbench.tests import smoke
from repro.models.moe import MoE
from repro.serve import ServingEngine

CELL = "deepseekv2-l5-decode-closed"

#: float32 program against the float32 reference: they differ by the
#: order of float32 sums only (absorbed against naive attention, the
#: gathered experts against the dense loop), ~1e-5 on logits of ~5
ATOL = RTOL = 2e-4


def published():
    with open(spec.BENCH_DIR / "configs" / "deepseek-v2-l5.json") as f:
        return json.load(f)


def toy_config(held_groups: int = 1) -> dict:
    c = published()
    c.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
             num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, vocab_size=256, num_experts_per_tok=4,
             router_experts=40, n_routed_experts=5 * held_groups,
             rope_scaling=dict(c["rope_scaling"],
                               original_max_position_embeddings=64))
    return c


PROMPT = 8

#: the toy cell in bf16 compares the mismatch share, as a mixture of
#: experts does: over 8 seeds (2**33 + 16 to 23) on the CPU the program
#: read 0-0.145, the float8 control 0.27-0.55
TOY_LIMITS = {"checks": {"mismatch_share": {"max": 0.2},
                         "tokens_compared": {"min": 10}}}


def _held(params, g: int):
    """The tree of a layer holding group ``g`` of a whole bank's tree:
    the experts' stacks cut to the group's 5."""
    st = params["stack"][0]
    experts = jax.tree.map(lambda a: a[:, 5 * g:5 * g + 5],
                           st["moe"]["experts"])
    return {**params, "stack": [{**st, "moe": {**st["moe"],
                                               "experts": experts}}]}


@pytest.fixture(scope="module")
def bank():
    """The toy with all 8 groups held, its float32 weights."""
    c = toy_config(held_groups=8)
    full = program.model_config(c, "toy").replace(dtype="float32")
    return c, full, program.make_params(full, 5, dtype=jnp.float32)


@pytest.fixture(scope="module")
def toy(bank):
    """The toy holding group 0, its reference's logits from the last
    prompt position on."""
    c = toy_config()
    cfg = program.model_config(c, "toy").replace(dtype="float32")
    params = _held(bank[2], 0)
    ref = check.reference_for(c, program.reference_weights(params, cfg, c))
    ids = np.random.default_rng(5).integers(0, cfg.vocab, 24)
    want = np.asarray(ref.logits(ids))[PROMPT - 1:]
    return cfg, params, ids, want


def _served(cfg, params, ids) -> np.ndarray:
    """Logits of the engine's calls from the last prompt position on:
    the prompt replayed through its cache, then one decode call a
    position."""
    eng = ServingEngine(cfg, params, max_len=32)
    logits, cache = eng.replay_prefill(ids[:PROMPT])
    out = [logits[0]]
    for s in range(PROMPT, len(ids)):
        logits, cache = eng._call("decode", jnp.asarray([ids[s]], jnp.int32),
                                  cache, s)
        out.append(logits[0])
    return np.asarray(jnp.stack(out))


def test_toy_takes_the_published_paths(toy):
    cfg = toy[0]
    assert cfg.expert_share and cfg.n_held == 5 and cfg.n_experts == 40
    assert MoE.local_path(cfg, 1) == "gathered"
    freqs = model_common.yarn_inv_freq(8, 1e4, cfg.rope_scaling)
    plain = 1e4 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(freqs / plain, [1, 0.5 + 0.5 / 40, 1 / 40,
                                               1 / 40], rtol=1e-12)


def test_engine_matches_the_reference(toy):
    cfg, params, ids, want = toy
    np.testing.assert_allclose(_served(cfg, params, ids), want, atol=ATOL,
                               rtol=RTOL)


def _depart(name: str, cfg, params, monkeypatch):
    """The program with one published mechanism left out."""
    if name == "no_mscale":         # softmax temperature mscale(40, .707)**2
        cfg = cfg.replace(rope_scaling=dict(cfg.rope_scaling, mscale=0,
                                            mscale_all_dim=0))
    elif name == "plain_rope_frequencies":
        monkeypatch.setattr(model_common, "yarn_inv_freq",
                            lambda dim, theta, s: 1.0 / theta ** (
                                np.arange(0, dim, 2) / dim))
    elif name == "plain_top_k":
        cfg = cfg.replace(n_group=0, topk_group=0)
    elif name == "renormalised_gates":
        cfg = cfg.replace(norm_topk_prob=True, routed_scaling_factor=1.0)
    elif name == "no_routed_scaling":
        cfg = cfg.replace(routed_scaling_factor=1.0)
    elif name == "no_shared_experts":
        moe = {k: v for k, v in params["stack"][0]["moe"].items()
               if k != "shared"}
        params = {**params, "stack": [{**params["stack"][0], "moe": moe}]}
    # a config of its own name, so that the engine's step is traced anew
    return cfg.replace(name=f"toy-{name}"), params


DEPARTURES = ("no_mscale", "plain_rope_frequencies", "plain_top_k",
              "renormalised_gates", "no_routed_scaling", "no_shared_experts")


@pytest.mark.parametrize("departure", DEPARTURES)
def test_each_departure_fails_the_comparison(toy, departure, monkeypatch):
    cfg, params, ids, want = toy
    got = _served(*_depart(departure, cfg, params, monkeypatch), ids)
    excess = np.abs(got - want) / (ATOL + RTOL * np.abs(want))
    assert excess.max() > 100, (departure, excess.max())


def test_held_shares_add_up_to_the_uncut_layer(bank):
    """Each of the 8 groups' held share, as the program computes it,
    less the shared experts that every chip computes alike, sums with
    the shared experts once to the reference's layer holding all 40."""
    c, full, params = bank
    assert not full.expert_share
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 12, full.d_model))
    moe = jax.tree.map(lambda a: a[0], params["stack"][0]["moe"])

    def shares(moe, x):
        shared = MoE._shared_tp(moe, full, x[0], None)
        total = shared
        for g in range(8):
            # 12 tokens a call: the grouped path, with room for every
            # token
            part = full.replace(experts_held=5, expert_offset=5 * g,
                                capacity_factor=8.0)
            held = {**moe, "experts": jax.tree.map(
                lambda a: a[5 * g:5 * g + 5], moe["experts"])}
            total = total + MoE._fwd_local(held, part, x)[0][0] - shared
        return total

    total = jax.jit(shares)(moe, x)
    lw = {**families.moe_view(moe), **families.mlp_view(moe["shared"],
                                                       "shared_")}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda lw, h: ref_mod.moe(c, lw, h, False))(lw, x[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_work_at_published_widths():
    """A call at position 0 reads 3.44 GB: 1.72 G weights in bf16 (the
    latent attention's projections, the dense MLP, the head, per MoE
    layer the router, 2 shared experts and 6 x 20 / 160 = 0.75 held
    experts) and two cache entries of 5 x 1,152 B."""
    c = published()
    w = ref_mod.work(c)
    assert w.attn_flops_per_key == 5 * (2 * 128 * 576 + 2 * 128 * 512)
    assert w.cache_bytes_per_token == 5 * 576 * 2
    assert counts.call_bytes(w, 0) == pytest.approx(3.44e9, rel=0.01)
    expert = 3 * 5120 * 1536
    assert w.multiplied - ref_mod.work(dict(c, n_routed_experts=0)) \
        .multiplied == 4 * 6 * 20 * expert // 160


def test_toy_cell_through_the_harness():
    """The toy family runs through the harness in bf16, ``correct``
    true, and its traced metrics read as the cell's entries list."""
    c = toy_config()
    cell = smoke.cell(CELL, c, smoke.closed_mix(), TOY_LIMITS)
    assert {m["name"] for m in cell.per_layer} == {
        f"{n}.mla" for n in ("compose_ms", "exec_ms_per_token",
                             "decode_step_roofline", "idle_share", "mfu")}
    run = harness.run_cell(cell, 2 ** 33 + 16, 2.0, False, time.perf_counter())
    assert run.correct, run.checks
    assert run.ctx["reference"].w["prefix"]["kv_b"].shape == (1, 16, 4 * 32)
