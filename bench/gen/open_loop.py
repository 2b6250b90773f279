"""Open-loop traffic: independent users, each request due at a fixed time.

A traffic file (``bench/traffic/<mix>.json``) with ``"generator":
"open_loop"`` gives:

* ``arrivals``: ``"poisson"`` at the cell's ``rate_req_s``, ``"backlog"``
  (every request due at time 0: a batch job's queue), or ``"flash_crowd"``
  (a Poisson base with ``burst_frac`` of the requests compressed into a
  burst ``burst_factor`` times the base rate, ``burst_at_frac`` of the way
  in; arrival code copied from ``repro/serving/traces.py``);
* ``prompt_len`` and ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}``, ``{"dist": "uniform", "min", "max"}`` or
  ``{"dist": "fixed", "value"}``;
* ``block``: requests come in blocks of this many.  Every block holds the
  same stratified set of prompt lengths, output lengths and Poisson gaps
  (the quantiles (i + 1/2) / block of each distribution, the gaps scaled so
  that a block lasts block / rate seconds), in an order drawn from the
  seed: every seed offers the same work in another order.

Token ids are uniform over the vocabulary and always drawn from the seed.
The same seed gives the same requests.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantiles(spec: Dict, k: int) -> np.ndarray:
    """The k stratified draws (quantiles (i + 1/2) / k) of a length
    distribution, as ints within its bounds."""
    u = (np.arange(k) + 0.5) / k
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(k, int(spec["value"]))
    if dist == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"] + 1)
        return np.clip(np.floor(v), spec["min"], spec["max"]).astype(int)
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.round(v), spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length distribution {dist!r}")


def max_len(spec: Dict) -> int:
    return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])


def _poisson_gaps(k: int, rate: float) -> np.ndarray:
    g = -np.log1p(-(np.arange(k) + 0.5) / k)
    return g * (k / rate) / g.sum()


def _flash_crowd(rs: np.random.RandomState, rate: float, n: int, *,
                 burst_frac: float, burst_factor: float,
                 burst_at_frac: float) -> np.ndarray:
    """Copied from ``repro.serving.traces.flash_crowd_trace`` (arrivals
    only; lengths come from the block draws here)."""
    n_burst = int(n * burst_frac)
    n_base = n - n_burst
    base = np.cumsum(rs.exponential(1.0 / rate, n_base))
    start = (base[-1] if n_base else 0.0) * burst_at_frac
    burst = start + np.cumsum(
        rs.exponential(1.0 / (rate * burst_factor), n_burst))
    return np.sort(np.concatenate([base, burst]))


def n_requests(traffic: Dict, cell: Dict, seconds: float) -> int:
    """Enough whole blocks that arrivals outlast the window; for a backlog,
    more work than the window can serve at ``backlog_tokens_per_s``."""
    k = traffic["block"]
    if traffic["arrivals"] == "backlog":
        mean_out = float(np.mean(quantiles(traffic["output_len"], k)))
        want = traffic["backlog_tokens_per_s"] * seconds / mean_out
    else:
        want = cell["rate_req_s"] * seconds + 1
    return k * (int(math.ceil(want / k)) + 1)


def generate(traffic: Dict, cell: Dict, seed: int, vocab: int,
             seconds: float) -> List[Dict]:
    """Requests as dicts {due (s from window start), tokens, max_new},
    sorted by due time."""
    rng = np.random.default_rng(seed)
    k = traffic["block"]
    n = n_requests(traffic, cell, seconds)
    plens = quantiles(traffic["prompt_len"], k)
    olens = quantiles(traffic["output_len"], k)
    kind = traffic["arrivals"]
    gaps = _poisson_gaps(k, cell["rate_req_s"]) if kind == "poisson" \
        else None
    p_all, o_all, g_all = [], [], []
    for _ in range(n // k):
        ip, io, ig = (rng.permutation(k) for _ in range(3))
        p_all.append(plens[ip])
        o_all.append(olens[io])
        if gaps is not None:
            g_all.append(gaps[ig])
    p_all, o_all = np.concatenate(p_all), np.concatenate(o_all)
    if kind == "poisson":
        due = np.cumsum(np.concatenate(g_all))
    elif kind == "backlog":
        due = np.zeros(n)
    elif kind == "flash_crowd":
        rs = np.random.RandomState(rng.integers(0, 2**31))
        due = _flash_crowd(rs, cell["rate_req_s"], n,
                           burst_frac=traffic["burst_frac"],
                           burst_factor=traffic["burst_factor"],
                           burst_at_frac=traffic["burst_at_frac"])
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    return [{"due": float(due[i]),
             "tokens": rng.integers(0, vocab, int(p_all[i]), dtype=np.int32),
             "max_new": int(o_all[i])} for i in range(n)]
