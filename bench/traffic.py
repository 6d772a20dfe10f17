"""The one traffic generator: every mix is a data file that it reads.

A mix (``traffic/<mix>.json``) gives the arrival kind and the distributions
of prompt and output lengths; a cell gives the load (a rate for an open
loop, a number of clients for a closed one).  Sizes and gaps are stratified:
``n`` draws are the distribution's quantiles at ``(i + 0.5) / n``, and the
seed only permutes them.  So every seed offers the same multiset of prompt
lengths, output lengths and arrival gaps, in another order, and two seeds
differ in the order of the work, never in its amount.  Token ids are drawn
from the seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it.  ``due`` is seconds after the
    window opens (open loop), or None where a client sends it when its last
    request ends (closed loop)."""

    idx: int
    prompt: np.ndarray
    max_new: int
    due: float | None


def _quantiles(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    v = np.clip(v, dist["min"], dist["max"])
    if "grid" in dist:   # nearest grid length: a bounded set of shapes
        grid = np.asarray(dist["grid"], float)
        v = grid[np.abs(v[:, None] - grid[None, :]).argmin(axis=1)]
    return np.rint(v).astype(np.int64)


def plan(traffic: dict, load: dict, *, seed: int, seconds: float,
         vocab: int) -> list[Planned]:
    """Requests of one run.  Open loop: ``round(rate * seconds)`` requests
    whose gaps are Exp(rate) quantiles, scaled to sum to ``seconds``, so
    every request is due inside the window and the offered rate is exact.
    Closed loop: a pool of ``requests_per_client * clients`` requests, sent
    in order."""
    rng = np.random.default_rng(seed)
    if traffic["arrivals"] == "open":
        n = max(1, round(load["rate_per_s"] * seconds))
    elif traffic["arrivals"] == "closed":
        n = traffic["requests_per_client"] * load["clients"]
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    prompts = rng.permutation(_quantiles(traffic["prompt"], n))
    outputs = rng.permutation(_quantiles(traffic["output"], n))
    due: list[float | None] = [None] * n
    if traffic["arrivals"] == "open":
        u = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-u))
        gaps *= seconds / gaps.sum()
        due = list(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
    return [Planned(i, rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o), None if d is None else float(d))
            for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]
