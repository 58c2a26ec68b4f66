"""The one traffic generator: turns a traffic file of parameters into the
requests of a run, from the seed.

Lengths come in blocks of ``block`` requests.  Every block holds the same
stratified draw from the length distribution (the quantiles at
``(i + 0.5) / block``), in an order the seed shuffles, so every seed asks
for the same amount of work and a run's window, which takes some hundreds
of requests, sees the same mix whatever the seed.  Online arrivals are an
open-loop Poisson stream whose gaps are drawn the same way.  Token ids are
uniform over the vocabulary, so no two prompts share a prefix.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Iterator, Optional

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """Stratified draw of ``n`` lengths: the distribution's quantiles at
    (i + 0.5) / n, rounded to whole tokens and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif dist["dist"] == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist["dist"] == "lognormal":  # a median and the sigma of the log
        z = np.array([statistics.NormalDist().inv_cdf(q) for q in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "exponential":  # inter-arrival gaps, mean 1 / rate
        x = -np.log1p(-u) / dist["rate_per_s"]
        return x
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


@dataclasses.dataclass
class Req:
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due_s: float = 0.0  # online: seconds after the window opens


class Traffic:
    def __init__(self, spec: dict, vocab: int):
        self.spec = spec
        self.vocab = vocab
        self.block = int(spec.get("block", 32))

    def _stream(self, part: dict, seed: int, stream: int) -> Iterator[Req]:
        rng = np.random.default_rng([seed, stream])
        plens = quantiles(part["prompt"], self.block)
        olens = quantiles(part["output"], self.block)
        while True:
            for n, m in zip(rng.permutation(plens), rng.permutation(olens)):
                toks = rng.integers(0, self.vocab, int(n), dtype=np.int64)
                yield Req(toks.astype(np.int32), int(m))

    def offline(self, seed: int) -> Iterator[Req]:
        """The offline backlog, in submission order (endless)."""
        return self._stream(self.spec["offline"], seed, 1)

    def online(self, seed: int, horizon_s: float) -> list:
        """Online arrivals due before ``horizon_s``, in due order."""
        part: Optional[dict] = self.spec.get("online")
        if not part:
            return []
        rng = np.random.default_rng([seed, 3])
        gaps = quantiles({"dist": "exponential", "min": 0, "max": 0,
                          "rate_per_s": part["rate_per_s"]}, self.block)
        reqs = self._stream(part, seed, 2)
        out, t = [], 0.0
        while True:
            for g in rng.permutation(gaps):
                t += float(g)
                if t >= horizon_s:
                    return out
                r = next(reqs)
                r.due_s = t
                out.append(r)

    def warmup(self, seed: int, n: Optional[int] = None) -> list:
        """Requests that warm the engine's programs: drawn apart from the
        window's, so they seed no prefix the window's prompts could hit."""
        w = self.spec["warmup"]
        rng = np.random.default_rng([seed, 4])
        return [Req(rng.integers(0, self.vocab, w["prompt"]).astype(np.int32), w["output"])
                for _ in range(w["requests"] if n is None else n)]
