"""Open-loop traffic from a mix file: one general generator.

A mix (``bench/traffic/<name>.json``) gives the length distributions and
the arrival process; the cell file (``bench/cells/<cell>.json``) gives the
offered rate. Arrivals are due on the wall clock, whatever the server
does, so a slow server meets a growing queue.

Every seed serves the same work. The schedule is built from blocks of
``block`` requests; each block holds the same ``block`` quantiles of the
prompt-length, output-length and inter-arrival distributions, permuted
within the block, so any stretch of whole blocks has the same length
histogram and the same duration. The seed draws the token ids. Where the
mix gives ``order_seed``, the permutations come from it, and every seed
serves the same lengths at the same times in the same order; otherwise
the seed draws them too.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float          # seconds after the schedule's start
    prompt: tuple
    max_new: int


def quantiles(dist: dict, n: int) -> list:
    """The ``n`` mid-point quantiles of a length distribution, as whole
    numbers clipped to ``[min, max]``."""
    kind = dist["dist"]
    out = []
    for j in range(n):
        u = (j + 0.5) / n
        if kind == "lognormal":
            v = dist["median"] * math.exp(dist["sigma"]
                                          * NormalDist().inv_cdf(u))
        elif kind == "fixed":
            v = dist["value"]
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(int(min(dist["max"], max(dist["min"], round(v)))))
    return out


def gap_quantiles(arrivals: dict, rate: float, n: int) -> list:
    """Inter-arrival gaps of one block, scaled so the block lasts exactly
    ``n / rate`` seconds."""
    kind = arrivals["process"]
    if kind == "poisson":
        g = [-math.log(1.0 - (j + 0.5) / n) for j in range(n)]
    elif kind == "uniform":
        g = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    s = sum(g)
    return [x * n / (rate * s) for x in g]


def schedule(mix: dict, *, rate: float, seed: int, horizon_s: float,
             vocab: int) -> list:
    """Arrivals due in ``[0, horizon_s)`` for ``rate`` requests per second.
    Token ids are drawn uniformly from ``[1, vocab)``."""
    q = int(mix["block"])
    plens = quantiles(mix["prompt_len"], q)
    olens = quantiles(mix["output_len"], q)
    gaps = gap_quantiles(mix["arrivals"], rate, q)
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(int(mix["order_seed"]))
             if "order_seed" in mix else rng)
    out, t, rid = [], 0.0, 0
    while True:
        pp, po, pg = (order.permutation(q) for _ in range(3))
        for j in range(q):
            t += gaps[pg[j]]
            if t >= horizon_s:
                return out
            prompt = tuple(int(x) for x in
                           rng.integers(1, vocab, size=plens[pp[j]]))
            out.append(Arrival(rid, t, prompt, olens[po[j]]))
            rid += 1
