"""The one traffic generator: a ``bench/traffic/<mix>.json`` file of
parameters and a seed in, a never-ending stream of request groups out.

Sizes are drawn in blocks: a block of ``strata`` consecutive draws takes
one value from each of ``strata`` equal-probability slices of the
distribution (the slice's mean), in an order drawn from ``ORDER_SEED``.  Prompt lengths are drawn per group, response lengths per
request (dealt to the members of consecutive groups in order).  So every
``--seed`` gets the same sizes in the same order: with a few dozen
requests in a window, the order alone moved tokens per second by a factor
of two.  The seed draws the token ids and the sampling keys.

Mix parameters:
  group_size                 requests sharing one prompt (GRPO's G)
  prompt_len   {dist: uniform, lo, hi, strata}
  response_len {dist: lognormal, mean, sigma, cap, strata}
                             ``mean`` is the mean before the cap
  response_cap               the response limit the engine is given
                             (max_total = prompt + response_cap); a
                             response ends at its drawn length as if EOS
                             had been sampled
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np

FIRST_ID = 3            # token ids below are PAD, BOS and EOS
ORDER_SEED = 0          # the order of the sizes in each block, for every mix
_N = NormalDist()


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def strata_means(dist: dict, n: int) -> List[float]:
    """Mean of each of ``n`` equal-probability slices of ``dist``."""
    if dist["dist"] == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        return [lo + (hi - lo) * (i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        sig = float(dist["sigma"])
        mu = math.log(dist["mean"]) - sig * sig / 2
        cap = float(dist.get("cap", math.inf))
        zc = (math.log(cap) - mu) / sig if math.isfinite(cap) else math.inf
        scale = math.exp(mu + sig * sig / 2)
        cdf = lambda z: _N.cdf(z) if math.isfinite(z) else float(z > 0)
        out = []
        for i in range(n):
            zl = _N.inv_cdf(i / n) if i else -math.inf
            zh = _N.inv_cdf((i + 1) / n) if i + 1 < n else math.inf
            part = 0.0
            if zl < zc:          # below the cap: the lognormal's own mass
                part += scale * (cdf(min(zh, zc) - sig) - cdf(zl - sig))
            if zh > zc:          # above it: held at the cap
                part += cap * (cdf(zh) - cdf(max(zl, zc)))
            out.append(part * n)
        return out
    raise ValueError(f"unknown distribution {dist['dist']!r}")


@dataclass
class Request:
    rid: int
    key: np.ndarray             # [2] uint32 sampling key
    drawn: int                  # response tokens before it ends


@dataclass
class Group:
    gid: int
    prompt: List[int]
    requests: List[Request] = field(default_factory=list)
    max_total: int = 0


class Stream:
    """``next(stream)`` -> the next :class:`Group`; deterministic per seed."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        self.mix = mix
        self.vocab = vocab_size
        self.rng = np.random.default_rng(int(seed) % 2**64)
        self.order = np.random.default_rng(ORDER_SEED)
        self.G = int(mix["group_size"])
        self._prompts: List[int] = []
        self._responses: List[int] = []
        self._gid = 0
        self._rid = 0

    def _refill(self, buf: List[int], dist: dict):
        vals = [max(1, int(round(v))) for v in strata_means(dist, dist["strata"])]
        buf.extend(self.order.permutation(vals).tolist())

    def __iter__(self):
        return self

    def __next__(self) -> Group:
        if not self._prompts:
            self._refill(self._prompts, self.mix["prompt_len"])
        L = self._prompts.pop(0)
        ids = self.rng.integers(FIRST_ID, self.vocab, size=L).tolist()
        g = Group(self._gid, ids, max_total=L + int(self.mix["response_cap"]))
        for _ in range(self.G):
            if not self._responses:
                self._refill(self._responses, self.mix["response_len"])
            d = min(self._responses.pop(0), int(self.mix["response_cap"]))
            key = self.rng.integers(0, 2**32, size=2, dtype=np.uint32)
            g.requests.append(Request(self._rid, key, d))
            self._rid += 1
        self._gid += 1
        return g
