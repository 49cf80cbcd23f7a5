"""Open-loop request arrivals for a serving cell.

Reads from the traffic file: ``clusters``, and under ``params``
``rate_per_s`` (mean over the window), ``prompt`` and ``budget`` (each
``{"lo", "hi", "exponent"}`` of a heavy tail P(k) ~ k^-exponent) and,
optionally, ``bursts`` (``{"on_s", "off_s"}``: arrivals only in the on
stretches, at the same mean rate over the window).  The vocabulary comes
from the configuration.  Everything is drawn from the run's ``--seed``.

Each window holds ``round(rate_per_s * window_s)`` requests: the
exponential (Poisson) mid-quantile gaps, scaled to span the window's
arrival time exactly, the heavy-tailed mid-quantile prompt lengths and
output budgets, and clusters split evenly.  So every seed offers the same
work in the window, and the seed only orders it and draws the prompt
tokens.  One more window of the same kind follows, so load is kept up
while the window's requests drain.
"""
from __future__ import annotations

import numpy as np


def heavy_tail_quantiles(lo: int, hi: int, n: int, exponent: float) -> np.ndarray:
    """``n`` integers on [lo, hi] at the mid-quantiles ``(i + 0.5) / n`` of
    P(k) proportional to k^-exponent: the same multiset for every seed."""
    k = np.arange(lo, hi + 1, dtype=np.float64)
    cdf = np.cumsum(k ** -exponent)
    cdf /= cdf[-1]
    return (lo + np.searchsorted(cdf, (np.arange(n) + 0.5) / n)).astype(np.int64)


def make(seed: int, cfg: dict, traffic: dict, window_s: float) -> list:
    """Dicts with ``uid``, ``due_s``, ``cluster``, ``prompt`` (int32) and
    ``budget``, sorted by ``due_s``."""
    p = traffic["params"]
    clusters, vocab = traffic["clusters"], cfg["vocab_size"]
    rng = np.random.default_rng(seed)
    n = max(1, round(p["rate_per_s"] * window_s))
    bursts = p.get("bursts")
    on, off = (bursts["on_s"], bursts["off_s"]) if bursts else (window_s, 0.0)
    q = (np.arange(n) + 0.5) / n
    base_gaps = -np.log1p(-q)
    base_gaps *= window_s * on / (on + off) / base_gaps.sum()
    lens0 = heavy_tail_quantiles(p["prompt"]["lo"], p["prompt"]["hi"], n, p["prompt"]["exponent"])
    budgets0 = heavy_tail_quantiles(p["budget"]["lo"], p["budget"]["hi"], n,
                                    p["budget"]["exponent"])
    reqs = []
    for w in range(2):
        gaps = rng.permutation(base_gaps)
        arrive = np.cumsum(gaps) - gaps  # the first at the window's start
        due = w * window_s + arrive + np.floor(arrive / on) * off
        lens, budgets = rng.permutation(lens0), rng.permutation(budgets0)
        cluster = rng.permutation(np.arange(n) % clusters)
        reqs += [{"uid": len(reqs) + i, "due_s": float(due[i]), "cluster": int(cluster[i]),
                  "prompt": rng.integers(0, vocab, size=int(lens[i]), dtype=np.int32),
                  "budget": int(budgets[i])} for i in range(n)]
    return reqs
