"""End-to-end system tests: paper-claim validation, training
integration, serving engine, distributed-vs-local MoE equivalence."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GTX580, EXPERIMENTS, greedy_order, percentile_rank,
                        simulate)
from repro.core.refine import refined_schedule


# --------------------------------------------------------------------------
# paper-claim validation (the reproduction's headline numbers)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(EXPERIMENTS))
def test_algorithm_near_optimal_per_experiment(name):
    """Deviation from optimal stays within the paper's reported band
    (paper: 0.02%..5.51%; we allow <=10%) on every experiment."""
    ks = EXPERIMENTS[name]()
    n = len(ks)
    sched = greedy_order(ks, GTX580)
    t_alg = simulate(sched.order, GTX580)
    if n <= 6:
        times = [simulate([ks[i] for i in p], GTX580)
                 for p in itertools.permutations(range(n))]
    else:
        import random
        rng = random.Random(0)
        times = [simulate([ks[i] for i in rng.sample(range(n), n)], GTX580)
                 for _ in range(1500)] + [t_alg]
    t_opt = min(times)
    assert t_alg / t_opt - 1 < 0.10, f"{name}: {t_alg / t_opt - 1:.2%}"


def test_refined_above_90th_percentile_everywhere():
    """Beyond-paper scheduler: >=90th percentile on every experiment."""
    import random
    for name, make in EXPERIMENTS.items():
        ks = make()
        n = len(ks)
        _, t_ref = refined_schedule(ks, GTX580, budget=600)
        if n <= 6:
            times = [simulate([ks[i] for i in p], GTX580)
                     for p in itertools.permutations(range(n))]
        else:
            rng = random.Random(0)
            times = [simulate([ks[i] for i in rng.sample(range(n), n)],
                              GTX580) for _ in range(1500)]
        assert percentile_rank(t_ref, times) >= 90.0, name


def test_ordering_matters_when_resources_stressed():
    """The design space must show a real spread for the stressed
    experiments (the paper's premise)."""
    ks = EXPERIMENTS["EpBsEsSw-8"]()
    import random
    rng = random.Random(1)
    times = [simulate([ks[i] for i in rng.sample(range(len(ks)),
                                                 len(ks))], GTX580)
             for _ in range(400)]
    assert max(times) / min(times) > 1.3


# --------------------------------------------------------------------------
# training integration (loss goes down through the full substrate)
# --------------------------------------------------------------------------

def test_train_loss_decreases(tmp_path):
    from repro.launch.train import train
    out = train("qwen1.5-0.5b", variant="smoke", steps=40,
                global_batch=4, seq_len=64, ckpt_dir=str(tmp_path),
                ckpt_every=0)
    assert out["last_loss"] < out["first_loss"] - 0.1


def test_train_resume_continues(tmp_path):
    from repro.launch.train import train
    out1 = train("qwen1.5-0.5b", variant="smoke", steps=10,
                 global_batch=2, seq_len=32, ckpt_dir=str(tmp_path),
                 ckpt_every=10)
    out2 = train("qwen1.5-0.5b", variant="smoke", steps=20,
                 global_batch=2, seq_len=32, ckpt_dir=str(tmp_path),
                 ckpt_every=10)
    # resumed run trained only steps 10..20
    assert len(out2["losses"]) == 10


# --------------------------------------------------------------------------
# serving engine
# --------------------------------------------------------------------------

def test_serving_engine_generates_and_orders():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, SchedulerPolicy, ServingEngine
    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = ServingEngine(cfg, params, max_len=32,
                        policy=SchedulerPolicy(kind="symbiotic"))
    eng.submit([Request(i, rng.integers(0, 512, size=4), max_new_tokens=4)
                for i in range(3)])
    stats = eng.run()
    assert stats["total_new_tokens"] >= 12
    assert all(len(v) >= 4 for v in stats["outputs"].values())
    assert stats["modelled_time_s"] > 0


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mixtral-8x7b",
                                  "deepseek-v2-236b"])
def test_default_policy_serves_benchmark_families(arch):
    """The policy the benchmark serves (``SchedulerPolicy()``) on each
    benchmarked family's smoke config: tokens equal arrival-order
    (fifo) serving, and every step makes exactly one decode call per
    live decoding request."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, SchedulerPolicy, ServingEngine
    cfg = get_config(arch, "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)

    def requests():
        rng = np.random.default_rng(1)
        return [Request(i, rng.integers(0, cfg.vocab, size=n),
                        max_new_tokens=4) for i, n in enumerate((5, 3, 6))]

    fifo = ServingEngine(cfg, params, max_len=32,
                         policy=SchedulerPolicy(kind="fifo"))
    fifo.submit(requests())
    want = fifo.run()["outputs"]
    eng = ServingEngine(cfg, params, max_len=32, policy=SchedulerPolicy())
    first, late = requests()[:2], requests()[2:]
    eng.submit(first)
    decode = eng.metrics.counter("decode_calls", kind="decode")
    steps = 0
    while True:
        if steps == 1:
            eng.submit(late)           # joins a step with live decodes
        live = sum(1 for r in eng.queue
                   if not r.done and r.cache is not None)
        before = decode.value
        if not eng.step():
            break
        assert decode.value - before == live
        steps += 1
    assert {r.rid: list(r.generated) for r in eng.queue} == want


def test_serving_warm_start_on_arrival():
    """A request joining a steady mix is a cache near-miss: the engine
    must adapt the cached composition (warm start) instead of
    recomputing, and generation must stay correct."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, SchedulerPolicy, ServingEngine
    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = ServingEngine(cfg, params, max_len=32,
                        policy=SchedulerPolicy(kind="symbiotic"))
    eng.submit([Request(i, rng.integers(0, 512, size=4), max_new_tokens=6)
                for i in range(3)])
    late = [Request(10, rng.integers(0, 512, size=4), max_new_tokens=4)]
    stats = eng.run(arrivals=[(2, late)])
    cache = stats["schedule_cache"]
    assert cache["warm_hits"] >= 1, cache
    assert all(len(v) >= 4 for v in stats["outputs"].values())


def test_serving_respect_deps_matches_flat_tokens():
    """The respect_deps path schedules per-layer chains (interior
    stages execute nothing) — generated tokens must be identical to
    the flat per-request path, and the composition must beat the
    dependency-aware fifo baseline's modelled time, or tie via the
    guard."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, SchedulerPolicy, ServingEngine
    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(0, 512, size=4), max_new_tokens=4)
                for i in range(3)]

    flat = ServingEngine(cfg, params, max_len=32,
                         policy=SchedulerPolicy(kind="symbiotic"))
    flat.submit(reqs())
    s_flat = flat.run()
    stats = {}
    for kind in ("fifo", "symbiotic"):
        eng = ServingEngine(cfg, params, max_len=32,
                            policy=SchedulerPolicy(kind=kind,
                                                   respect_deps=True))
        eng.submit(reqs())
        stats[kind] = eng.run()
        assert stats[kind]["outputs"] == s_flat["outputs"], kind
        # per-layer granularity: a 4-layer smoke config cannot finish
        # a request in fewer than 8 chained stages -> >= 8 rounds/step
        assert stats[kind]["rounds"] > s_flat["rounds"]
    # the symbiotic DAG composition never models worse than the
    # dep-aware fifo baseline (the _compose_dag guard guarantees it)
    assert (stats["symbiotic"]["modelled_time_s"]
            <= stats["fifo"]["modelled_time_s"] + 1e-12)


def test_serving_greedy_decode_deterministic():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, ServingEngine
    cfg = get_config("qwen1.5-0.5b", "smoke")
    params = T.init(jax.random.PRNGKey(0), cfg)
    outs = []
    for _ in range(2):
        eng = ServingEngine(cfg, params, max_len=16)
        eng.submit([Request(0, np.arange(4), max_new_tokens=4)])
        outs.append(eng.run()["outputs"][0])
    assert outs[0] == outs[1]


# --------------------------------------------------------------------------
# distributed MoE == local MoE (shard_map correctness on a 1x1 mesh)
# --------------------------------------------------------------------------

def test_moe_distributed_matches_local():
    from repro.dist.context import act_ctx
    from repro.launch.mesh import make_host_mesh
    from repro.models.common import ModelConfig
    from repro.models.moe import MoE
    cfg = ModelConfig(name="m", n_layers=2, d_model=32, n_heads=4,
                      n_kv_heads=4, head_dim=8, d_ff=64, vocab=64,
                      n_experts=4, top_k=2, n_shared_experts=1,
                      moe_d_ff=48, dtype="float32")
    p = MoE.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y_local, aux_local = MoE._fwd_local(p, cfg, x)
    mesh = make_host_mesh()
    with jax.set_mesh(mesh), act_ctx(dp="data", tp="model", mesh=mesh):
        y_ep, aux_ep = jax.jit(
            lambda pp, xx: MoE._fwd_ep(pp, cfg, xx))(p, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_local["moe_lb_loss"]),
                               float(aux_ep["moe_lb_loss"]), rtol=1e-3)


# --------------------------------------------------------------------------
# TPU round model sanity
# --------------------------------------------------------------------------

def test_symbiotic_round_beats_split_rounds():
    """One mixed prefill+decode round is faster than prefill-only +
    decode-only rounds (the weight stream is paid once)."""
    from repro.core.tpu import (decode_profile, make_serving_device,
                                prefill_profile, round_time)
    dev = make_serving_device()
    w = 14e9
    p = prefill_profile("p", n_params=7e9, seq_len=2048,
                        kv_bytes_per_token=131072)
    ds = [decode_profile(f"d{i}", n_params=7e9, kv_len=4096,
                         kv_bytes_per_token=131072) for i in range(8)]
    mixed = round_time([p] + ds, dev, w)
    split = round_time([p], dev, w) + round_time(ds, dev, w)
    assert mixed < split


# --------------------------------------------------------------------------
# elastic restart: checkpoint saved on one mesh restores onto another
# --------------------------------------------------------------------------

def test_elastic_checkpoint_reshard(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import restore_checkpoint, save_checkpoint
    tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
    save_checkpoint(str(tmp_path), 3, tree)
    # "new cluster": a (1,1) mesh with explicit shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shard = {"w": NamedSharding(mesh, P("data", "model"))}
    restored, _ = restore_checkpoint(str(tmp_path), tree, shardings=shard)
    assert restored["w"].sharding.is_equivalent_to(shard["w"], 2)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


# --------------------------------------------------------------------------
# robustness: refined scheduler on random workloads (the paper's ">90th
# percentile" claim, generalised beyond its six hand-picked experiments)
# --------------------------------------------------------------------------

def test_refined_robust_on_random_workloads():
    import random
    from repro.core import GTX580
    from repro.core.resources import bs_kernel, ep_kernel, es_kernel, \
        sw_kernel
    rng = random.Random(42)
    pcts = []
    for trial in range(8):
        ks = []
        for i in range(5):
            fam = rng.choice([ep_kernel, bs_kernel, es_kernel, sw_kernel])
            ks.append(fam(f"k{i}", grid=rng.choice([16, 32, 48]),
                          shm=rng.choice([0, 8192, 16384])))
        _, t_ref = refined_schedule(ks, GTX580, budget=400)
        times = [simulate([ks[i] for i in p], GTX580)
                 for p in itertools.permutations(range(5))]
        pcts.append(percentile_rank(t_ref, times))
    assert sum(pcts) / len(pcts) >= 90.0, pcts
