"""The one generator of serving traffic: a traffic file's parameters and a
seed in, a list of requests with their due times out.

Arrivals are the mix's Poisson process over the window, given its count:
the expected number of requests (``rate_per_s`` x the window), due at
sorted uniform times drawn from the seed.  Given how many arrivals fall
in a window, a Poisson process places them so, so bursts and lulls are
as Poisson makes them; only the count itself, which would change how much
work a run holds, is the same for every seed.  Every seed gets the same
multiset of prompt and output lengths — stratified quantiles of the mix's
distributions — in an order drawn from the seed and balanced: each run of
``BLOCK`` consecutive requests holds one length from each of ``BLOCK``
strata, so no seed packs its long requests into one part of the window.
"""
from __future__ import annotations

import dataclasses
import numpy as np


@dataclasses.dataclass
class Planned:
    idx: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    gen_len: int
    keys: dict              # request attribute -> feature key


BLOCK = 8


def balanced(values: np.ndarray, rng) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every block of
    ``BLOCK`` consecutive entries takes one value from each of ``BLOCK``
    strata of the sorted values."""
    v = np.sort(values)
    picks = [rng.permutation(s) for s in np.array_split(np.arange(len(v)),
                                                        BLOCK)]
    order = []
    for k in range(-(-len(v) // BLOCK)):
        order += list(rng.permutation([p[k] for p in picks if k < len(p)]))
    return v[np.asarray(order, dtype=np.int64)]


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _inv_normal(p: np.ndarray) -> np.ndarray:
    from statistics import NormalDist
    nd = NormalDist()
    return np.array([nd.inv_cdf(float(x)) for x in p])


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of ``spec``: a lognormal
    (``median``, ``sigma``) clipped to [``min``, ``max``]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * np.exp(spec["sigma"] * _inv_normal(_quantiles(n)))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrivals(spec: dict, n: int, seconds: float, rng) -> np.ndarray:
    """Due times of ``n`` arrivals over ``[0, seconds)``: ``poisson`` is the
    Poisson process given its count, ``n`` sorted uniform draws."""
    if spec["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {spec['arrivals']!r}")
    return np.sort(rng.uniform(0.0, seconds, n))


def zipf_keys(rng, n_keys: int, s: float, n: int) -> np.ndarray:
    """``n`` keys of ``range(n_keys)`` with Zipf(``s``) popularity; which
    key is hot is drawn from the seed."""
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -s
    ranks = rng.choice(n_keys, size=n, p=p / p.sum())
    return rng.permutation(n_keys)[ranks]


def plan(traffic: dict, seconds: float, seed: int, vocab: int,
         key_space: dict) -> list[Planned]:
    """The requests due within ``seconds``, in due order.

    ``key_space`` maps a request attribute (``drug_id``) to
    ``(n_keys, zipf_s)``."""
    n = max(1, round(float(traffic["rate_per_s"]) * seconds))
    rng = np.random.default_rng(seed)
    p_len = balanced(lengths(traffic["prompt_len"], n), rng)
    g_len = balanced(lengths(traffic["gen_len"], n), rng)
    due = arrivals(traffic, n, seconds, rng)
    keys = {a: zipf_keys(rng, nk, s, n) for a, (nk, s) in key_space.items()}
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(p_len[i]), dtype=np.int32)
        out.append(Planned(i, float(due[i]), prompt, int(g_len[i]),
                           {a: int(k[i]) for a, k in keys.items()}))
    return out
