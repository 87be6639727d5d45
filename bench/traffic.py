"""The one traffic generator: requests from a traffic file and a seed.

Lengths and arrival gaps come from a fixed grid of quantiles of each
distribution, in blocks: every block of `block` draws holds the same
values, and the seed only shuffles them inside each block and picks the
token ids. So every seed offers the same work in another order, and any
prefix of the stream has nearly the same mix. With `"same_order": true`
the shuffle is the same for every seed and the seed picks only the
token ids: a closed loop then does the same work in every window.

Distributions (in a traffic file's `prompt`, `output`, `gap`):
  {"dist": "fixed", "value": v}
  {"dist": "uniform", "lo": a, "hi": b}            integers a..b
  {"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}
  {"dist": "exponential", "mean": m}               seconds (arrivals)
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

BLOCK = 16


def quantiles(spec: dict, n: int) -> list:
    """The n values of a distribution at probabilities (i + 1/2) / n."""
    ps = [(i + 0.5) / n for i in range(n)]
    kind = spec["dist"]
    if kind == "fixed":
        return [spec["value"]] * n
    if kind == "uniform":
        lo, hi = spec["lo"], spec["hi"]
        return [int(lo + math.floor(p * (hi - lo + 1))) for p in ps]
    if kind == "lognormal":
        z = NormalDist()
        mu = math.log(spec["median"])
        return [int(min(spec["hi"], max(spec["lo"], round(
            math.exp(mu + spec["sigma"] * z.inv_cdf(p)))))) for p in ps]
    if kind == "exponential":
        return [-spec["mean"] * math.log(1.0 - p) for p in ps]
    raise ValueError(f"unknown distribution {kind!r}")


def stream(spec: dict, count: int, rng: np.random.Generator) -> list:
    """`count` values in blocks of the same quantile grid, each block
    shuffled by `rng`."""
    grid = quantiles(spec, BLOCK)
    out: list = []
    while len(out) < count:
        out += [grid[i] for i in rng.permutation(BLOCK)]
    return out[:count]


def requests(traffic: dict, vocab: int, seed: int, count: int) -> list:
    """`count` requests: dicts with rid, prompt (int32 ids), max_new and,
    for an open loop, due (seconds after the window opens)."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(0) if traffic.get("same_order") else rng
    plens = stream(traffic["prompt"], count, order)
    outs = stream(traffic["output"], count, order)
    reqs = [{"rid": i, "max_new": int(o),
             "prompt": rng.integers(0, vocab, size=int(p), dtype=np.int32)}
            for i, (p, o) in enumerate(zip(plens, outs))]
    if "rate" in traffic:
        gaps = stream({"dist": "exponential", "mean": 1.0 / traffic["rate"]},
                      count, order)
        due = np.cumsum(gaps)
        for r, t in zip(reqs, due):
            r["due"] = float(t)
    return reqs
