"""One general, seeded load generator, driven by a traffic file.

Every run gets the SAME schedule of sizes and due times: lengths and
inter-arrival gaps are the stratified quantiles of the distributions the
file names, cut into blocks of ``block`` requests, and the order within
each block is drawn from the file's ``schedule_seed`` (a replayed trace:
a tail over some tens of requests moves with the order alone, PERF.md
section 2). The run's seed draws the token ids, and the weights. Nothing
here imports the program.

Traffic file keys this module reads (all under ``"requests"``):
  prompt_tokens / output_tokens: {"dist": "lognormal", "median", "sigma",
      "min", "max"} or {"dist": "fixed", "value"}
  block: requests per block (one stratum each)
  pairing_seed: fixes which prompt length meets which output length
  schedule_seed: fixes the order of lengths and gaps
  arrivals: {"mode": "backlog", "depth_per_slot"} or
            {"mode": "open", "rate_rps"} (Poisson gaps)
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def stratified(dist: dict, n: int) -> np.ndarray:
    """The n mid-stratum quantiles of ``dist``, as integers >= 1."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    nd = NormalDist()
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    q = np.array([math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
                  for i in range(n)])
    return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)


def stratified_gaps(rate: float, n: int) -> np.ndarray:
    """n mid-stratum exponential gaps with mean 1/rate (seconds)."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g / g.mean() / rate                 # exact mean: the set is fixed


class RequestStream:
    """An endless, seeded stream of requests: ``next()`` returns
    ``(prompt ids, max_new_tokens, gap_s)`` — ``gap_s`` is the time from
    the previous request's due instant (0 in backlog mode)."""

    def __init__(self, params: dict, seed: int, vocab: int):
        self.p = params
        self.vocab = vocab
        self.block = int(params["block"])
        self.rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self.order_rng = np.random.default_rng(
            [int(params["schedule_seed"]), 0x5C4ED])
        fixed = np.random.default_rng(int(params.get("pairing_seed", 0)))
        self.prompts = stratified(params["prompt_tokens"], self.block)
        self.outputs = stratified(params["output_tokens"],
                                  self.block)[fixed.permutation(self.block)]
        arr = params["arrivals"]
        self.mode = arr["mode"]
        if self.mode == "open":
            self.gaps = stratified_gaps(float(arr["rate_rps"]), self.block)
        elif self.mode == "backlog":
            self.gaps = np.zeros(self.block)
        else:
            raise ValueError(f"unknown arrivals mode {self.mode!r}")
        self._queue: list = []
        self.count = 0

    def _refill(self) -> None:
        order = self.order_rng.permutation(self.block)
        gaps = self.gaps[self.order_rng.permutation(self.block)]
        for i, g in zip(order, gaps):
            self._queue.append((int(self.prompts[i]), int(self.outputs[i]),
                                float(g)))

    def next(self):
        if not self._queue:
            self._refill()
        n_prompt, n_out, gap = self._queue.pop(0)
        ids = self.rng.integers(0, self.vocab, size=n_prompt, dtype=np.int32)
        self.count += 1
        return ids, n_out, gap


def token_stream(seed: int, vocab: int, n_tokens: int) -> np.ndarray:
    """A seeded order-1 Markov token stream for LM training (learnable:
    each token prefers four successors with probability 0.8), so that the
    loss of later steps depends on the update and not only on the init.
    Built from precomputed draws; the walk itself is the only loop."""
    rng = np.random.default_rng([int(seed), 0x70CE25])
    prefs = rng.integers(0, vocab, size=(vocab, 4), dtype=np.int32)
    stay = rng.random(n_tokens) < 0.8
    pick = rng.integers(0, 4, size=n_tokens)
    jump = rng.integers(0, vocab, size=n_tokens, dtype=np.int32)
    out = np.empty(n_tokens, np.int32)
    tok = int(jump[0])
    for i in range(n_tokens):
        out[i] = tok
        tok = int(prefs[tok, pick[i]]) if stay[i] else int(jump[i])
    return out


def percentile_nearest_rank(values, q: float) -> float:
    """The smallest value with at least q% of the sample at or below it."""
    ys = sorted(values)
    if not ys:
        raise ValueError("percentile of an empty sample")
    k = max(0, math.ceil(q / 100.0 * len(ys)) - 1)
    return ys[k]
