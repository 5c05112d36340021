"""Decide ``correct``: the served tokens against the plain reference.

After the window, a sample of the finished requests, drawn from the
seed and holding the longest of them, is run once through the family's
float32 reference: each prompt followed by the tokens the program
served. At every position where the program chose a token, the gap is
how far that token's reference logit lies below the reference's best
logit there. A greedy program that computes what the configuration
states picks the reference's best token, or one within rounding of it;
one that computes something else picks tokens far below it.

From the gaps come the numbers a cell may compare: the widest gap, the
mean gap, and the share of positions whose token is not the
reference's best. Which of them a cell compares, each limit, and the
readings it was set from are in ``chipbench/limits/<workload>.json``.
A dense model compares the widest gap. A mixture of experts compares
the share: where two experts' routing weights nearly tie, bf16
rounding picks the other expert and that one token's gap is as wide
as a wrong model's, so its widest gap cannot tell the two apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import spec

__all__ = ["Served", "sample", "family", "reference_for", "gaps",
           "gap_numbers", "verdict"]


class Served:
    """One request as the program served it."""

    __slots__ = ("rid", "prompt", "tokens")

    def __init__(self, rid: int, prompt, tokens):
        self.rid, self.prompt, self.tokens = rid, np.asarray(prompt), \
            list(tokens)


def sample(finished: list[Served], seed: int, min_tokens: int,
           max_requests: int) -> list[Served]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                           -r.rid))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng([int(seed) & (2 ** 63 - 1), 99]) \
        .permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def family(config: dict, bench_dir=spec.BENCH_DIR):
    """The reference module of the configuration's family, found by its
    ``model_type``: ``<bench_dir>/reference/<model_type>.py``."""
    return spec.load_family("reference", config, bench_dir)


def reference_for(config: dict, weights: dict, bench_dir=spec.BENCH_DIR):
    """The family's plain reference over ``weights``."""
    return family(config, bench_dir).reference(config, weights)


@jax.jit
def _gaps(ref_logits, pick_logits, targets, valid):
    """Per position: reference best minus the reference logit of the
    token picked (``targets`` where given, else the argmax of
    ``pick_logits``); 0 where not valid."""
    best = jnp.max(ref_logits, -1)
    pick = jnp.where(targets >= 0, targets,
                     jnp.argmax(pick_logits, -1)).astype(jnp.int32)
    got = jnp.take_along_axis(ref_logits, pick[:, None], -1)[:, 0]
    return jnp.where(valid, best - got, 0.0)


def gaps(ref, served: list[Served], length: int, control: bool = False
         ) -> list[np.ndarray]:
    """Per request, the gap at each position that produced a served
    token. ``control``: the gap of the token the control (the reference
    in float8) puts first, at the same positions, on the same inputs."""
    out = []
    for r in served:
        S, n = len(r.prompt), len(r.tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                   np.int32)])
        if len(seq) > length:
            raise ValueError(f"request {r.rid}: {len(seq)} positions > "
                             f"{length}")
        ids = np.zeros(length, np.int32)
        ids[:len(seq)] = seq
        targets = np.full(length, -1, np.int32)
        valid = np.zeros(length, bool)
        valid[S - 1:S - 1 + n] = True
        if not control:
            targets[S - 1:S - 1 + n] = r.tokens
        ref_logits = ref.logits(ids)
        pick = ref.logits(ids, control=True) if control else ref_logits
        g = _gaps(ref_logits, pick, jnp.asarray(targets), jnp.asarray(valid))
        out.append(np.asarray(g)[valid])
    return out


def gap_numbers(per_request: list[np.ndarray], prefix: str = "") -> dict:
    """The numbers a check may compare, over every compared position:
    the widest gap, the mean gap, and the share of positions whose
    token is not the reference's best."""
    g = np.concatenate(per_request)
    return {prefix + "max_logit_gap": float(g.max()),
            prefix + "mean_logit_gap": float(g.mean()),
            prefix + "mismatch_share": float(np.mean(g > 0))}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``numbers``: name -> value. Each limit is ``{"max": x}`` or
    ``{"min": x}``. Returns (correct, {name: {"value", "limit"}})."""
    ok, checks = True, {}
    for name, lim in limits.items():
        v = numbers.get(name)
        if "max" in lim:
            bound, good = lim["max"], v is not None and v <= lim["max"]
        else:
            bound, good = lim["min"], v is not None and v >= lim["min"]
        ok &= bool(good)
        checks[name] = {"value": v, "limit": bound}
    return ok, checks
