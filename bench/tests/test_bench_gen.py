"""The traffic generator is deterministic for a seed, keeps its declared
bounds, and gives every seed the same work in another order."""
import collections
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

from gen import open_loop  # noqa: E402

TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))
BIG_SEED = 2**33 + 12345
# a mix of Poisson arrivals and heavy-tailed lengths, beside the files
POISSON = {"generator": "open_loop", "arrivals": "poisson", "block": 6,
           "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.9,
                          "min": 32, "max": 1024},
           "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                          "min": 8, "max": 256}}


def _traffic(name):
    if name == "poisson_inline":
        return dict(POISSON)
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", TRAFFIC + ["poisson_inline"])
def test_same_seed_same_requests_and_bounds(name):
    tr = _traffic(name)
    cell = {"rate_req_s": 0.25}
    a = open_loop.generate(tr, cell, BIG_SEED, 49152, 30.0)
    b = open_loop.generate(tr, cell, BIG_SEED, 49152, 30.0)
    c = open_loop.generate(tr, cell, BIG_SEED + 1, 49152, 30.0)
    assert len(a) == len(b) == len(c) and len(a) % tr["block"] == 0
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert any(not np.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(a, c))
    due = [r["due"] for r in a]
    assert due == sorted(due)
    for key, f in (("prompt_len", lambda r: r["tokens"].size),
                   ("output_len", lambda r: r["max_new"])):
        spec = tr[key]
        lo = spec.get("min", spec.get("value"))
        hi = open_loop.max_len(spec)
        assert all(lo <= f(r) <= hi for r in a)
        # every block holds the same multiset, whatever the seed
        k = tr["block"]
        blocks = [collections.Counter(f(r) for r in reqs[i:i + k])
                  for reqs in (a, c) for i in range(0, len(reqs), k)]
        assert all(bl == blocks[0] for bl in blocks)
    assert all(0 <= t < 49152 for r in a for t in r["tokens"])
    if tr["arrivals"] == "poisson":
        # a block lasts block / rate seconds
        assert due[tr["block"] - 1] == pytest.approx(tr["block"] / 0.25)
        assert due[-1] > 30.0
    if tr["arrivals"] == "backlog":
        assert max(due) == 0.0


def test_lognormal_quantiles_hand_worked():
    spec = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 10,
            "max": 1000}
    q = open_loop.quantiles(spec, 2)
    # quantiles 1/4 and 3/4: z = -/+0.6745, 100 * exp(-/+0.6745)
    assert list(q) == [51, 196]
    assert list(open_loop.quantiles({"dist": "uniform", "min": 64,
                                     "max": 128}, 4)) == [72, 88, 104, 120]
    assert list(open_loop.quantiles({"dist": "fixed", "value": 5}, 3)) \
        == [5, 5, 5]


def test_flash_crowd_arrivals_are_seeded_and_sorted():
    tr = dict(POISSON, arrivals="flash_crowd", burst_frac=0.2,
              burst_factor=4.0, burst_at_frac=0.5)
    a = open_loop.generate(tr, {"rate_req_s": 1.0}, 7, 100, 20.0)
    b = open_loop.generate(tr, {"rate_req_s": 1.0}, 7, 100, 20.0)
    due = [r["due"] for r in a]
    assert due == [r["due"] for r in b] and due == sorted(due)
