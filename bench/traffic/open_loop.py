"""Open-loop traffic: requests arrive on a schedule whatever the server
is doing, as from independent users.

A mix gives ``rate_rps`` and the ``prompt`` and ``output`` length
distributions (``lognormal`` with ``median`` and ``sigma``, clipped to
``[min, max]``). For a window of S seconds exactly ``round(rate * S)``
requests arrive. Every seed gets the same work: the lengths are the
quantiles ``(i + 1/2) / n`` of each distribution, paired by one fixed
permutation, and the gaps between arrivals are the same quantiles of an
exponential distribution (a Poisson process's gaps), scaled to fill the
window. The seed orders the requests and the gaps, and draws the token
ids, uniform over the vocabulary.

A mix may also give ``strata``, k: the order is then stratified, so
that each block of k consecutive arrivals holds one request from each
k-quantile of prompt length and one gap from each k-quantile of gap
length (``stratified``). Every stretch of the window then carries the
same share of the work, and a tail over the window depends less on where
the seed happened to bunch long prompts or short gaps.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

PAIRING_SEED = 20240101     # fixed: pairs prompt and output quantiles


@dataclass
class Arrival:
    due: float              # seconds after the window opens
    prompt: list
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n quantile lengths of one distribution, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def work(mix: dict, seconds: float) -> list[tuple[int, int]]:
    """The (prompt, output) length pairs of a window, in fixed order."""
    n = int(round(mix["rate_rps"] * seconds))
    if n < 1:
        raise ValueError(f"rate {mix['rate_rps']} over {seconds} s sends "
                         f"no request")
    p = lengths(mix["prompt"], n)
    o = lengths(mix["output"], n)
    o = o[np.random.default_rng(PAIRING_SEED).permutation(n)]
    return list(zip(p.tolist(), o.tolist()))


def stratified(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """An order of 0..n-1 (indices ascending by size) made of blocks of
    consecutive places, each block taking one index from each of at most
    k strata of ceil(n / k) consecutive indices; where the top stratum is
    short, the last blocks lack it and are one place shorter. The seed
    deals each stratum over the blocks and orders each block."""
    m = -(-n // k)
    blocks = [[] for _ in range(m)]
    for lo in range(0, n, m):
        stratum = np.arange(lo, min(lo + m, n))
        for b, i in enumerate(rng.permutation(stratum)):
            blocks[b].append(int(i))
    return np.asarray([i for blk in blocks for i in rng.permutation(blk)])


def generate(mix: dict, seconds: float, seed: int,
             vocab: int) -> list[Arrival]:
    """The window's requests, sorted by due time."""
    pairs = work(mix, seconds)
    n = len(pairs)
    rng = np.random.default_rng(seed)
    k = int(mix.get("strata", 0))
    if k > 1:
        order = stratified(rng, n, k)
        gaps = -np.log1p(-_quantiles(n))[stratified(rng, n, k)]
    else:
        order = rng.permutation(n)
        gaps = -np.log1p(-_quantiles(n))[rng.permutation(n)]
    # each request is due in the middle of its gap: all inside the window
    due = (np.cumsum(gaps) - gaps / 2) * (seconds / gaps.sum())
    out = []
    for k, i in enumerate(order):
        plen, olen = pairs[i]
        toks = rng.integers(0, vocab, plen).tolist()
        out.append(Arrival(float(due[k]), toks, int(olen)))
    return out
