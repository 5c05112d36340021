#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # four one-chip replicas

One chip: builds qwen1.5-0.5b at its full published width from a
seeded random init (bf16 compute, as the engine runs it), serves a few
requests through :class:`repro.serve.ServingEngine`, checks the served
tokens, compares each request's replayed-prefill logits with
:func:`repro.models.transformer.prefill_logits`, serves the same
requests again and requires identical tokens, then runs the compiled
Pallas kernels against their XLA twins in :mod:`repro.models`.

``--four-chips`` runs only the replica path: four replicas behind
:class:`repro.serve.ServingFrontend`, replica ``i`` on
``jax.devices()[i]``, against one replica on the same requests.

Every line before the last is smoke output, not a benchmark result.
The last line is one JSON object naming the device.  Without a TPU
the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.attention import (  # noqa: E402
    causal_mask_bias, decode_sdpa, sdpa)
from repro.models.common import rmsnorm  # noqa: E402
from repro.serve import (AdmissionPolicy, Request, ServingEngine,  # noqa: E402
                         ServingFrontend)

ARCH = "qwen1.5-0.5b"
MAX_LEN = 256
N_REQUESTS = 4
NEW_TOKENS = 8
PROMPT_LEN = (16, 64)            # inclusive range of prompt lengths

#: Engine prefill (one decode step per prompt token) against
#: ``prefill_logits`` (one causal forward over the prompt), both in
#: bf16: the two orders of summation may differ by this share of the
#: largest reference logit.  A wrong position, mask or cache entry
#: moves logits by their own size.  Where the argmaxes differ, the
#: engine's pick must be within the same bound of the reference's top
#: logit (a near-tie), or the comparison fails.
LOGIT_RTOL = 2.0 ** -4

#: Compiled kernel against its XLA twin on bf16 inputs: the kernels
#: keep softmax and norm statistics in f32 where the twins round some
#: intermediates to bf16, so outputs of magnitude <= ~4 may differ by a
#: few bf16 ulps (2**-6 at 2..4).
KERNEL_ATOL = 2.0 ** -4

#: Kernel shapes at qwen1.5-0.5b widths: prefill of 512 tokens, decode
#: of 4 rows against a 1024-slot cache, and 512 rows of rmsnorm.
KERNEL_SEQ = 512
KERNEL_BATCH = 4
KERNEL_CACHE = 1024


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def make_requests(vocab: int, seed: int, n: int = N_REQUESTS,
                  new_tokens: int = NEW_TOKENS) -> list[Request]:
    """``n`` seeded requests with prompts of ``PROMPT_LEN`` tokens."""
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    sizes = rng.integers(lo, hi + 1, size=n)
    return [Request(i, rng.integers(0, vocab, size=int(s)),
                    max_new_tokens=new_tokens)
            for i, s in enumerate(sizes)]


def check_tokens(outputs: dict, reqs: list[Request], vocab: int) -> None:
    """Every request got its ``max_new_tokens`` tokens, all in
    ``[0, vocab)``."""
    for r in reqs:
        toks = outputs.get(r.rid)
        _check(toks is not None, f"request {r.rid} got no output")
        _check(len(toks) == r.max_new_tokens,
               f"request {r.rid}: {len(toks)} tokens, wanted "
               f"{r.max_new_tokens}")
        bad = [t for t in toks if not 0 <= t < vocab]
        _check(not bad, f"request {r.rid}: tokens {bad} outside "
               f"[0, {vocab})")


def compare_logits(engine_logits, ref_logits) -> dict:
    """Compare one prompt's last-token logits, engine against
    reference.  Returns the numbers; raises :class:`SmokeFailure` past
    the bound."""
    e = np.asarray(engine_logits, np.float32).reshape(-1)
    r = np.asarray(ref_logits, np.float32).reshape(-1)
    _check(e.shape == r.shape, f"logit shapes {e.shape} vs {r.shape}")
    _check(bool(np.all(np.isfinite(e))), "engine logits not finite")
    tol = LOGIT_RTOL * float(np.max(np.abs(r)))
    diff = float(np.max(np.abs(e - r)))
    ae, ar = int(np.argmax(e)), int(np.argmax(r))
    _check(diff <= tol, f"max |engine - reference| {diff} > {tol}")
    _check(ae == ar or r[ae] >= r[ar] - tol,
           f"argmax {ae} vs reference {ar}, reference gap "
           f"{float(r[ar] - r[ae])} > {tol}")
    return {"max_abs_diff": diff, "tol": tol, "argmax_equal": ae == ar}


def serve_and_check(cfg, params, seed: int) -> dict:
    """Serve seeded requests, check the tokens, compare each prefill
    with ``prefill_logits``, and serve them again for identical
    tokens.  Returns what was compared."""
    out = {}
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, max_len=MAX_LEN)
    reqs = make_requests(cfg.vocab, seed)
    eng.submit(reqs)
    stats = eng.run()
    out["serve_wall_s"] = time.perf_counter() - t0
    check_tokens(stats["outputs"], reqs, cfg.vocab)
    out["outputs"] = stats["outputs"]

    t0 = time.perf_counter()
    ref_fn = jax.jit(T.prefill_logits, static_argnums=(1,))
    out["prefill"] = []
    for r in reqs:
        e_logits, _ = eng.replay_prefill(r.prompt)
        _check(int(jnp.argmax(e_logits[0])) == stats["outputs"][r.rid][0],
               f"request {r.rid}: replayed prefill disagrees with the "
               "served first token")
        r_logits = ref_fn(params, cfg, jnp.asarray(r.prompt, jnp.int32)[None])
        out["prefill"].append(dict(rid=r.rid, prompt_len=len(r.prompt),
                                   **compare_logits(e_logits, r_logits)))
    out["compare_wall_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    again = ServingEngine(cfg, params, max_len=MAX_LEN)
    again.submit(make_requests(cfg.vocab, seed))
    _check(again.run()["outputs"] == stats["outputs"],
           "second run of the same requests gave other tokens")
    out["rerun_wall_s"] = time.perf_counter() - t0
    return out


def compare_kernels(cfg, seed: int, *, interpret: bool = False) -> dict:
    """Run ``ops.flash_attention``, ``ops.decode_attention`` and
    ``ops.rmsnorm`` at ``cfg``'s widths against their XLA twins;
    returns each max-abs difference."""
    H, Hkv, D, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    seq, batch, cache = KERNEL_SEQ, KERNEL_BATCH, KERNEL_CACHE
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16
    scale = 1.0 / float(np.sqrt(D))

    def normal(k, shape):
        return jax.random.normal(k, shape, bf)

    q = normal(ks[0], (1, seq, H, D))
    k = normal(ks[1], (1, seq, Hkv, D))
    v = normal(ks[2], (1, seq, Hkv, D))
    flash = ops.flash_attention(q, k, v, causal=True, interpret=interpret)
    flash_x = sdpa(q, k, v, causal_mask_bias(seq, seq, causal=True,
                                             window=None), scale=scale)

    qd = normal(ks[3], (batch, H, D))
    kc = normal(ks[4], (batch, cache, Hkv, D))
    vc = normal(ks[5], (batch, cache, Hkv, D))
    lengths = jax.random.randint(ks[6], (batch,), 1, cache + 1)
    dec = ops.decode_attention(qd, kc, vc, lengths, interpret=interpret)
    mask = jnp.arange(cache)[None, :] < lengths[:, None]
    dec_x = decode_sdpa(qd, kc, vc, mask, scale=scale)

    x = normal(ks[7], (seq, d))
    g = 1.0 + 0.1 * jax.random.normal(ks[0], (d,), jnp.float32)
    rms = ops.rmsnorm(x, g, interpret=interpret)
    rms_x = rmsnorm({"scale": g}, x)

    out = {}
    for name, a, b in (("flash_attention", flash, flash_x),
                       ("decode_attention", dec, dec_x),
                       ("rmsnorm", rms, rms_x)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        _check(a.shape == b.shape, f"{name}: shape {a.shape} vs {b.shape}")
        _check(bool(np.all(np.isfinite(a))), f"{name}: not finite")
        diff = float(np.max(np.abs(a - b)))
        _check(diff <= KERNEL_ATOL,
               f"{name}: max |kernel - XLA| {diff} > {KERNEL_ATOL}")
        out[name] = diff
    return out


def serve_replicas(cfg, params, seed: int, devices) -> dict:
    """Serve the seeded requests on ``len(devices)`` replicas, replica
    ``i`` on ``devices[i]``, and on one replica on ``devices[0]``;
    the tokens must be identical.  Returns each replica's device id."""
    runs = {}
    for placement in (list(devices), list(devices[:1])):
        fe = ServingFrontend.build(
            cfg, params, n_replicas=len(placement), placement=placement,
            max_len=MAX_LEN, admission=AdmissionPolicy(route="round_robin"))
        reqs = make_requests(cfg.vocab, seed, len(devices))
        t0 = time.perf_counter()
        fe.run([(0.0, r) for r in reqs])
        wall = time.perf_counter() - t0
        check_tokens(fe.outputs(), reqs, cfg.vocab)
        ids = []
        for eng, dev in zip(fe.engines, placement):
            held = {d.id for leaf in jax.tree.leaves(eng.params)
                    for d in leaf.devices()}
            _check(held == {dev.id}, f"replica params on {held}, "
                   f"placed on {dev.id}")
            for r in eng.queue:
                cached = {d.id for leaf in jax.tree.leaves(r.cache)
                          for d in leaf.devices()}
                _check(cached == {dev.id}, f"request {r.rid} cache on "
                       f"{cached}, replica on {dev.id}")
            ids.append(dev.id)
        runs[len(placement)] = {"outputs": fe.outputs(), "device_ids": ids,
                                "wall_s": wall,
                                "served": [len(e.queue) for e in fe.engines]}
    many, one = runs[len(devices)], runs[1]
    _check(many["outputs"] == one["outputs"],
           f"{len(devices)} replicas and one replica gave other tokens")
    return {"device_ids": many["device_ids"], "served": many["served"],
            "wall_s": many["wall_s"], "one_replica_wall_s": one["wall_s"],
            "outputs": many["outputs"]}


def _param_bytes(params) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(params))


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica path and its "
                         "one-replica comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    _say(f"cache dir {enable_compile_cache()}")
    _say(f"device {dev.device_kind} x{len(devices)}; smoke output, "
         "not benchmark results")

    cfg = get_config(ARCH, "full")
    t0 = time.perf_counter()
    params = jax.block_until_ready(T.init(jax.random.PRNGKey(args.seed), cfg))
    _say(f"{cfg.name} init {time.perf_counter() - t0:.3f}s, "
         f"{T.count_params(params)} params, {_param_bytes(params)} bytes")

    if args.four_chips:
        rep = serve_replicas(cfg, params, args.seed, devices[:4])
        _say(f"replicas on device ids {rep['device_ids']}, requests per "
             f"replica {rep['served']}, wall {rep['wall_s']:.3f}s "
             f"(one replica {rep['one_replica_wall_s']:.3f}s)")
        _check(len(set(rep["device_ids"])) == 4,
               f"replicas share devices: {rep['device_ids']}")
        _say(f"4 replicas == 1 replica tokens: {rep['outputs']}")
    else:
        t0 = time.perf_counter()
        probe = ServingEngine(cfg, params, max_len=MAX_LEN)
        jax.block_until_ready(probe.replay_prefill(np.zeros(1, np.int32)))
        _say(f"decode_step first call (compile + 1 step) "
             f"{time.perf_counter() - t0:.3f}s")
        out = serve_and_check(cfg, params, args.seed)
        _say(f"served {len(out['outputs'])} requests in "
             f"{out['serve_wall_s']:.3f}s: {out['outputs']}")
        for p in out["prefill"]:
            _say(f"prefill vs prefill_logits: {p}")
        _say(f"compare {out['compare_wall_s']:.3f}s, identical rerun "
             f"{out['rerun_wall_s']:.3f}s")
        t0 = time.perf_counter()
        kern = compare_kernels(cfg, args.seed)
        _say(f"compiled kernels vs XLA twins (max |diff|, bound "
             f"{KERNEL_ATOL}): {kern} in {time.perf_counter() - t0:.3f}s")
    _say(f"peak_bytes_in_use {_peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
