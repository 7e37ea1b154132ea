"""The traffic generator: deterministic per seed, the same set of sizes
for every seed, and the length mixes as stated."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np
import pytest

from bench import spec, traffic

LONGTAIL = traffic.load(spec.BENCH / "traffic" / "rollout-longtail.json")
LONGPROMPT = traffic.load(spec.BENCH / "traffic" / "rollout-longprompt.json")


def _take(mix, seed, n, vocab=151936):
    s = traffic.Stream(mix, seed, vocab)
    return [next(s) for _ in range(n)]


def test_same_seed_same_stream():
    a, b = _take(LONGTAIL, 2**31 + 99, 6), _take(LONGTAIL, 2**31 + 99, 6)
    for x, y in zip(a, b):
        assert x.prompt == y.prompt and x.max_total == y.max_total
        assert [(r.rid, r.drawn, tuple(r.key)) for r in x.requests] == \
               [(r.rid, r.drawn, tuple(r.key)) for r in y.requests]
    c = _take(LONGTAIL, 2**31 + 100, 6)
    assert [g.prompt for g in a] != [g.prompt for g in c]


def test_every_seed_gets_the_same_sizes_in_the_same_order(monkeypatch):
    sizes = lambda mix, seed: [(len(g.prompt), [r.drawn for r in g.requests])
                               for g in _take(mix, seed, 16)]
    for mix in (LONGTAIL, LONGPROMPT):
        assert sizes(mix, 1) == sizes(mix, 2**40 + 3)
    prompts = [p for p, _ in sizes(LONGTAIL, 1)]
    assert min(prompts) >= 256 and max(prompts) <= 1024
    assert len(set(prompts)) == 16              # one of each slice
    first = sizes(LONGTAIL, 1)
    monkeypatch.setattr(traffic, "ORDER_SEED", 1)
    assert sizes(LONGTAIL, 1) != first


def test_longtail_mix_as_stated():
    g = _take(LONGTAIL, 7, 1)[0]
    assert len(g.requests) == 8                      # one GRPO group
    assert g.max_total == len(g.prompt) + 14336
    drawn = [r.drawn for r in g.requests]
    # the capped lognormal's mean, held by the slice means of 8 slices
    sig, mu, cap = 1.0, math.log(3000) - 0.5, 14336
    n = NormalDist()
    zc = (math.log(cap) - mu) / sig
    capped_mean = (math.exp(mu + sig * sig / 2) * n.cdf(zc - sig)
                   + cap * (1 - n.cdf(zc)))
    assert np.mean(traffic.strata_means(LONGTAIL["response_len"], 8)) == \
        pytest.approx(capped_mean, rel=1e-9)
    assert abs(np.mean(drawn) - capped_mean) < 1.0   # rounding only
    assert max(drawn) <= 14336 and min(drawn) >= 1


def test_longprompt_mix_as_stated():
    gs = _take(LONGPROMPT, 3, 64)
    assert all(len(g.requests) == 1 for g in gs)
    lens = sorted(len(g.prompt) for g in gs)
    assert lens[0] >= 2048 and lens[-1] <= 8192
    assert np.mean(lens) == pytest.approx(5120, abs=1)
    drawn = [g.requests[0].drawn for g in gs]
    assert max(drawn) <= 1024 and np.mean(drawn) == pytest.approx(192, rel=0.01)
    assert all(3 <= t < 151936 for g in gs[:4] for t in g.prompt)
