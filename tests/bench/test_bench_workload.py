"""The traffic generator: deterministic per seed, the same work for every
seed, and the laws and sizes its mix file states."""
import json
import math
import os
import sys
from collections import Counter

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.workload import draw_gaps, draw_lengths, schedule, segment_sizes  # noqa: E402

MIXES = ["chat-burst", "chat-steady"]


def mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    m = mix(name)
    a = schedule(m, 10, 2**31 + 17, 1000)
    b = schedule(m, 10, 2**31 + 17, 1000)
    assert [(r.due, r.prompt, r.max_new) for r in a] == [(r.due, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    a = schedule(m, 10, 3, 1000)
    b = schedule(m, 10, 2**33 + 5, 1000)
    for seg in ("ramp", "window", "tail"):
        ra = [r for r in a if r.segment == seg]
        rb = [r for r in b if r.segment == seg]
        assert Counter(len(r.prompt) for r in ra) == Counter(len(r.prompt) for r in rb)
        assert Counter(r.max_new for r in ra) == Counter(r.max_new for r in rb)
        ga = sorted(np.diff([r.due for r in ra]))
        gb = sorted(np.diff([r.due for r in rb]))
        assert len(ga) == len(gb)
    # in another order
    assert [(r.due, len(r.prompt), r.max_new) for r in a] != [(r.due, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_segments_hold_rate_times_length_requests(name):
    m = mix(name)
    seconds = 45
    reqs = schedule(m, seconds, 1, 1000)
    sizes = segment_sizes(m, seconds)
    for seg, (start, length, n) in sizes.items():
        rs = [r for r in reqs if r.segment == seg]
        assert len(rs) == n == max(1, round(m["rate"] * length))
        assert all(start <= r.due < start + length for r in rs)
        assert rs[0].due == pytest.approx(start)
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    m = mix(name)
    reqs = schedule(m, 45, 9, 1000)
    buckets = set(m["prompt"]["buckets"])
    assert {len(r.prompt) for r in reqs} <= buckets
    assert max(len(r.prompt) for r in reqs) <= m["max_prefill"]
    outs = [r.max_new for r in reqs]
    assert min(outs) >= m["output"]["min"] and max(outs) <= m["output"]["max"]
    # every request fits its slot's context
    assert max(buckets) + m["output"]["max"] <= m["context"]
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


def test_length_laws():
    rng = np.random.default_rng(0)
    # mean 58.45 and sigma 0.8: median 58.45 exp(-0.32) = 42.44, clipped far out
    x = draw_lengths({"law": "lognormal", "mean": 58.45, "sigma": 0.8, "min": 8, "max": 4096}, 200_000, rng)
    assert np.median(x) == pytest.approx(58.45 * math.exp(-0.32), rel=0.02)
    assert x.mean() == pytest.approx(58.45 + 0.5, rel=0.02)  # ceil adds half a token
    assert x.min() >= 8 and x.max() <= 4096
    b = draw_lengths({"law": "lognormal", "mean": 256 * math.exp(0.405), "sigma": 0.9,
                      "buckets": [64, 128, 256, 512, 1024]}, 200_000, rng)
    # a draw rounds up to the next bucket: P(x <= 256) = 1/2 for median 256
    assert np.mean(b <= 256) == pytest.approx(0.5, abs=0.01)
    assert set(np.unique(b)) <= {64, 128, 256, 512, 1024}


@pytest.mark.parametrize("law,cv", [({"law": "poisson"}, 1.0), ({"law": "gamma", "cv": 2.0}, 2.0)])
def test_gap_laws(law, cv):
    g = draw_gaps(law, 5.0, 400_000, np.random.default_rng(1))
    assert g.mean() == pytest.approx(0.2, rel=0.02)
    assert g.std() / g.mean() == pytest.approx(cv, rel=0.03)
    assert math.isclose(len(g), 400_000)
