"""The one traffic generator: reads a mix's parameters and the seed.

Sizes and gaps are not drawn at random. They are quantiles of the
stated distribution at evenly spaced levels (a stratified set), put in
an order that the mix's own ``order_seed`` fixes. The run's seed draws
only the token ids. Every seed so offers the same work at the same
times: with batch-1 execution the order decides how requests queue
behind each other, and orders that differ by seed moved the
time-to-first-token tail by tens of percent between seeds. The order
is stratified: the sorted quantiles are cut into ``block`` strata, and
every block of ``block`` consecutive requests takes one value from
each stratum, so a window that sees a few blocks sees the whole
distribution.

The Poisson gaps are the arrival process of ``serve.loadgen``'s
``poisson_arrivals`` (exponential with mean ``1/rate``), taken as
quantiles in the same way.

A closed loop whose requests outlast the window starts aged where the
mix sets ``aged_start`` (:func:`aged`): its first requests enter part
way through their outputs, so the window holds requests at every
stage of their lives rather than one batch at its start.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["Request", "quantiles", "stratified_order", "open_loop",
           "ClosedLoopSource", "aged"]

#: stream ids: one independent numpy stream per quantity and seed
_PROMPT, _OUTPUT, _GAP, _TOKENS, _AGE = 1, 2, 3, 4, 5


@dataclass
class Request:
    """One request as offered: what the program is sent, and when."""
    rid: int
    due: float              # seconds after the window opens (open loop)
    prompt: np.ndarray      # (S,) int32 token ids
    max_new_tokens: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` quantiles of ``dist`` at levels ``(i + 0.5) / n``, sorted.

    ``{"dist": "lognormal", "median" or "mean", "sigma", "min", "max"}``
    gives integers clipped to ``[min, max]`` (a source that publishes a
    mean states it as such: the median is then ``mean * exp(-sigma**2 /
    2)``); ``{"dist": "exponential", "rate"}`` gives floats."""
    levels = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        z = NormalDist()
        s = dist["sigma"]
        mu = math.log(dist["median"]) if "median" in dist else \
            math.log(dist["mean"]) - s * s / 2
        v = [round(math.exp(mu + s * z.inv_cdf(u))) for u in levels]
        return np.clip(np.asarray(v, np.int64), dist["min"], dist["max"])
    if dist["dist"] == "exponential":
        return np.asarray([-math.log1p(-u) / dist["rate"] for u in levels])
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def stratified_order(values: np.ndarray, block: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Order sorted ``values`` (length a multiple of ``block``) so that
    every block of ``block`` consecutive entries holds one value of each
    of the ``block`` strata of the sorted list; which value of a stratum
    lands in which block, and the order inside a block, come from
    ``rng``."""
    n = len(values)
    if n % block:
        raise ValueError(f"{n} values do not fill blocks of {block}")
    m = n // block
    strata = values.reshape(block, m)
    picks = np.stack([strata[s][rng.permutation(m)] for s in range(block)])
    out = picks.T.copy()                      # (m rounds, block)
    for r in range(m):
        out[r] = out[r][rng.permutation(block)]
    return out.reshape(-1)


def _rng(seed: int, stream: int, epoch: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream, epoch])


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> list[Request]:
    """Every request of an open loop due in ``[0, seconds)``.

    The stratified set is drawn for ``rate * seconds`` requests rounded
    up to whole blocks, its gaps scaled to a mean of ``1 / rate``, and
    the requests due before ``seconds`` are kept: every seed offers
    the same rate and nearly the same count."""
    rate, block = float(mix["rate_per_s"]), int(mix["block"])
    n = block * max(1, math.ceil(rate * seconds / block))
    gaps = quantiles({"dist": "exponential", "rate": rate}, n)
    gaps *= (n / rate) / gaps.sum()
    order = int(mix["order_seed"])
    gaps = stratified_order(gaps, block, _rng(order, _GAP))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    plen = stratified_order(quantiles(mix["prompt_len"], n), block,
                            _rng(order, _PROMPT))
    olen = stratified_order(quantiles(mix["output_len"], n), block,
                            _rng(order, _OUTPUT))
    tok = _rng(seed, _TOKENS)
    reqs = [Request(k, float(due[k]), _tokens(tok, int(plen[k]), vocab),
                    int(olen[k])) for k in range(n)]
    return [r for r in reqs if r.due < seconds]


class ClosedLoopSource:
    """Endless requests for a closed loop, drawn a block at a time.

    Epoch ``e`` holds ``requests_per_epoch`` requests in the stratified
    order of :func:`stratified_order`; epochs follow each other, each
    from its own stream of the seed, so a faster program never runs
    out of work and still sees the same distribution."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.block = int(mix["block"])
        self.per_epoch = int(mix["requests_per_epoch"])
        self._buf: list[Request] = []
        self._epoch = 0
        self._next_rid = 0

    def _fill(self) -> None:
        n, e = self.per_epoch, self._epoch
        order = int(self.mix["order_seed"])
        plen = stratified_order(quantiles(self.mix["prompt_len"], n),
                                self.block, _rng(order, _PROMPT, e))
        olen = stratified_order(quantiles(self.mix["output_len"], n),
                                self.block, _rng(order, _OUTPUT, e))
        tok = _rng(self.seed, _TOKENS, e)
        for k in range(n):
            self._buf.append(Request(self._next_rid, 0.0,
                                     _tokens(tok, int(plen[k]), self.vocab),
                                     int(olen[k])))
            self._next_rid += 1
        self._epoch += 1

    def take(self, k: int) -> list[Request]:
        while len(self._buf) < k:
            self._fill()
        out, self._buf = self._buf[:k], self._buf[k:]
        return out


def aged(reqs: list[Request], mix: dict, seed: int, vocab: int
         ) -> list[Request]:
    """``reqs`` as requests that have already served part of their
    outputs: request ``i`` has served ``floor(u_i * max_new_tokens)``
    tokens, the ``u_i`` the levels ``(j + 0.5) / n`` in an order the
    mix's ``order_seed`` fixes. The served tokens (ids drawn from the
    seed) join its prompt, and it asks for the rest; every seed ages
    the same requests by the same counts."""
    n = len(reqs)
    order = np.random.default_rng([int(mix["order_seed"]), _AGE])
    u = (order.permutation(n) + 0.5) / n
    tok = _rng(seed, _AGE)
    out = []
    for r, ui in zip(reqs, u):
        a = int(ui * r.max_new_tokens)
        out.append(Request(r.rid, r.due,
                           np.concatenate([r.prompt, _tokens(tok, a, vocab)]),
                           r.max_new_tokens - a))
    return out
