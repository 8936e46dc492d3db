"""The one traffic generator: every traffic mix is a JSON file of its
parameters (``chipbench/traffic/<name>.json``) that this module reads.

Arrival arithmetic follows ``repro.serve.trace.bursty_trace`` (Poisson
bursts, geometric burst sizes, log-normal lengths), copied so that the
yardstick does not move with the program.  Two changes make runs steady:

* Sizes are the distribution's stratified quantiles, not random draws,
  and bursts, gaps and the order of sizes come from one fixed base seed.
  So every ``--seed`` sends the same work at the same times: the seed
  draws the token ids (and the driver the weights) and only reorders the
  requests inside each burst, which leaves the queue's work unchanged.
* Arrivals are in seconds of the wall clock from the start of the window:
  the driver submits each request when it is due and times it from then.

All draws use ``random.Random`` seeded through sha256, whose algorithms are
stable across Python versions, so a seed names the same traffic everywhere.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


def derive_rng(*parts) -> random.Random:
    digest = hashlib.sha256(":".join(map(str, parts)).encode()).digest()
    return random.Random(int.from_bytes(digest[:8], "little"))


def derive_seed32(*parts) -> int:
    digest = hashlib.sha256(":".join(map(str, parts)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    arrival_s: float        # due time, seconds after the window opens
    prompt_len: int
    max_new: int


def quantile_sizes(n: int, dist: dict) -> List[int]:
    """``n`` stratified quantiles of a clamped log-normal.

    ``dist``: {"median", "sigma", "min", "max"}; quantile i sits at
    probability (i + 0.5) / n, so any ``n`` reproduces the distribution's
    shape without sampling noise."""
    nd = NormalDist()
    mu = np.log(float(dist["median"]))
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(float(np.exp(mu + float(dist["sigma"]) * z))))
        out.append(max(int(dist["min"]), min(int(dist["max"]), v)))
    return out


def _pairs(n: int, traffic: dict) -> List[tuple]:
    """(prompt_len, max_new) pairs with a fixed, seed-independent pairing."""
    prompts = quantile_sizes(n, traffic["prompt"])
    outputs = quantile_sizes(n, traffic["output"])
    derive_rng("pairing", traffic["base_seed"], n).shuffle(outputs)
    return list(zip(prompts, outputs))


def open_loop(traffic: dict, seconds: float, seed: int,
              rate_rps: float = None) -> List[Req]:
    """The arrivals of one window of an open loop.

    Bursts arrive as a Poisson process at ``rate_rps / mean_burst`` per
    second with geometric sizes of mean ``mean_burst``; the trace is drawn
    once from ``base_seed``, and ``seed`` only shuffles the (prompt,
    output) pairs inside each burst.
    """
    rate = float(traffic["rate_rps"] if rate_rps is None else rate_rps)
    mean_burst = max(float(traffic["mean_burst"]), 1.0)
    base = derive_rng("bursts", traffic["base_seed"], rate, seconds)
    gaps, bursts, t = [], [], 0.0
    while True:
        gap = base.expovariate(rate / mean_burst)
        if t + gap >= seconds:
            break
        t += gap
        size = 1
        while base.random() < 1.0 - 1.0 / mean_burst:
            size += 1
        gaps.append(gap)
        bursts.append(size)
    pairs = _pairs(sum(bursts), traffic)
    base.shuffle(pairs)
    order = derive_rng("order", seed)
    out, t, k = [], 0.0, 0
    for gap, size in zip(gaps, bursts):
        t += gap
        burst = pairs[k:k + size]
        order.shuffle(burst)
        for p, m in burst:
            out.append(Req(k, t, p, m))
            k += 1
    return out


def closed_pool(traffic: dict) -> Iterator[Req]:
    """Endless requests for a closed loop, in blocks of ``block``: every
    block holds the same (prompt, output) pairs, in an order drawn from
    ``base_seed``, so every run sends the same sizes in the same order
    (its seed draws only their token ids, from each request's index)."""
    pairs = _pairs(int(traffic["block"]), traffic)
    k = 0
    for b in range(1 << 30):
        blk = list(pairs)
        derive_rng("block", traffic["base_seed"], b).shuffle(blk)
        for p, m in blk:
            yield Req(k, 0.0, p, m)
            k += 1


def prompt_tokens(seed: int, req: Req, vocab: int) -> np.ndarray:
    """Token ids of one prompt, from the seed and the request's index."""
    gen = np.random.default_rng(derive_seed32("tokens", seed, req.index))
    return gen.integers(0, vocab, size=req.prompt_len, dtype=np.int32)
