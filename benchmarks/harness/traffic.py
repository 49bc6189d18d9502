"""The one general generator of serving traffic. A traffic mix is a data
file of parameters (benchmarks/traffic/<name>.json); this reads it.

Every seed gets the SAME multiset of request sizes and inter-arrival gaps,
in another order, so that a seed changes which request meets which, not how
much work a run holds. The traffic is a sequence of blocks of 32 requests:
within every block the sizes are the stratified quantiles of the file's
clipped lognormal distributions and the gaps the stratified quantiles of an
exponential, each block in an order of its own drawn from the run's seed.
So any window of a run holds nearly the same work under every seed, and a
block of arrivals always spans block / rate seconds. Prompt tokens and
sampling seeds are drawn from the run's seed.

This arrival process (`arrival: stratified_exponential`) is NOT a Poisson
process, though its gaps have an exponential's shape: every block of 32
spans exactly 32 / rate seconds, so the count in a long interval has none
of a Poisson count's variance, and the longest gap is the last of 32
quantiles (4.2 means), so bursts and lulls are capped. Queueing and the
tail of the time to first token are therefore milder than true Poisson
arrivals at the same rate would give; by how much is not measured
(PERF.md, Open questions).

Arithmetic and the shape of a request follow `scripts/loadgen.py make_trace`
(seeded arrivals, lengths, per-request sampling seeds); its `replay` runs on
a virtual clock and is not used.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


BLOCK = 32


@dataclass
class Arrival:
    due_s: float            # relative to the window's start; < 0 = lead
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    top_k: int
    top_p: float
    seed: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """The n stratified quantiles ((i + 0.5) / n) of a lognormal with the
    given median and log-space sigma, rounded and clipped to [lo, hi]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    """The n stratified quantiles of an exponential with mean 1/rate,
    rescaled so that they sum to exactly n / rate."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g * (n / rate) / g.sum()


def n_requests(traffic: dict, seconds: float) -> int:
    """How many requests the mix holds for a window of `seconds`: the rate
    times lead + window + tail (open loop), or the backlog's fixed depth
    per second of window."""
    if traffic["arrival"] == "backlog":
        n = traffic["backlog_requests_per_window_s"] * seconds
    else:
        n = traffic["rate_per_s"] * (traffic["lead_s"] + seconds
                                     + traffic["tail_s"])
    return int(math.ceil(n / BLOCK)) * BLOCK


def make_arrivals(traffic: dict, seed: int, seconds: float,
                  vocab: int) -> List[Arrival]:
    n, block = n_requests(traffic, seconds), BLOCK
    p, o = traffic["prompt_len"], traffic["output_len"]
    rng = np.random.RandomState(seed % (2 ** 32))

    def blocks(values):
        """The block's values once per block, each block in its own order
        (independent orders: a long prompt does not imply a long answer)."""
        return np.concatenate([values[rng.permutation(block)]
                               for _ in range(n // block)])

    prompts = blocks(lognormal_quantiles(block, p["median"], p["sigma"],
                                         p["min"], p["max"]))
    outputs = blocks(lognormal_quantiles(block, o["median"], o["sigma"],
                                         o["min"], o["max"]))
    if traffic["arrival"] == "backlog":
        due = np.full(n, -float("inf"))
    elif traffic["arrival"] == "stratified_exponential":
        gaps = blocks(exponential_quantiles(block, traffic["rate_per_s"]))
        due = np.cumsum(gaps) - traffic["lead_s"]
    else:
        raise ValueError(f"arrival {traffic['arrival']!r}: expected "
                         "'stratified_exponential' or 'backlog'")
    greedy_every = traffic["greedy_every"]
    s = traffic["sampling"]
    order = blocks(np.arange(block))    # which requests are the greedy ones
    out = []
    for i in range(n):
        greedy = order[i] % greedy_every == 0
        out.append(Arrival(
            due_s=float(due[i]),
            prompt=rng.randint(0, vocab, int(prompts[i])).tolist(),
            max_new_tokens=int(outputs[i]),
            temperature=0.0 if greedy else s["temperature"],
            top_k=0 if greedy else s["top_k"],
            top_p=1.0 if greedy else s["top_p"],
            seed=int(rng.randint(0, 2 ** 31 - 1))))
    return out
