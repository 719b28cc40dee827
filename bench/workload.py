"""The one traffic generator: a schedule of requests from a mix's parameters.

A mix (``bench/traffic/<name>.json``) gives the length laws, the arrival
law and its rate.  The schedule has three segments on the window's clock:
a ramp before the window (``[-ramp, 0)``), the window (``[0, seconds)``)
and a tail after it (``[seconds, seconds + tail_cap)``) that keeps the
load on while the window's last requests finish.

Every seed gets the same work: each segment's multiset of prompt
lengths, output lengths and inter-arrival gaps is drawn once from the
mix's fixed ``pool_seed``, and the run's seed permutes them, so it
changes which request comes when.  The seed also draws the prompt tokens.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

__all__ = ["Req", "draw_lengths", "draw_gaps", "schedule", "segment_sizes"]

SEGMENTS = ("ramp", "window", "tail")


@dataclass
class Req:
    idx: int
    segment: str
    due: float  # seconds from the window's start (negative in the ramp)
    prompt: List[int]
    max_new: int


def draw_lengths(law: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a lognormal law of a given mean (before rounding),
    rounded up to a bucket or clipped."""
    assert law["law"] == "lognormal", law
    sigma = law["sigma"]
    x = rng.lognormal(math.log(law["mean"]) - sigma**2 / 2, sigma, n)
    if "buckets" in law:
        b = np.asarray(sorted(law["buckets"]))
        return b[np.minimum(np.searchsorted(b, x, side="left"), len(b) - 1)]
    return np.clip(np.ceil(x), law["min"], law["max"]).astype(np.int64)


def draw_gaps(law: Dict[str, Any], rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps with mean ``1/rate``: Poisson arrivals
    (exponential gaps) or gamma gaps of a given coefficient of variation."""
    if law["law"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if law["law"] == "gamma":
        shape = 1.0 / law["cv"] ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival law {law['law']!r}")


def segment_sizes(mix: Dict[str, Any], seconds: float) -> Dict[str, tuple]:
    """(start, length, count) of each segment on the window's clock."""
    spans = {
        "ramp": (-float(mix["ramp_seconds"]), float(mix["ramp_seconds"])),
        "window": (0.0, float(seconds)),
        "tail": (float(seconds), float(mix["tail_cap_seconds"])),
    }
    return {k: (s, ln, max(1, round(mix["rate"] * ln))) for k, (s, ln) in spans.items()}


def schedule(mix: Dict[str, Any], seconds: float, seed: int, vocab: int) -> List[Req]:
    """All requests of one run, in due order."""
    run = np.random.default_rng(int(seed) % 2**64)
    out: List[Req] = []
    for si, (seg, (start, length, n)) in enumerate(segment_sizes(mix, seconds).items()):
        pool = np.random.default_rng([int(mix["pool_seed"]), si, n])
        prompts = draw_lengths(mix["prompt"], n, pool)
        outputs = draw_lengths(mix["output"], n, pool)
        gaps = draw_gaps(mix["arrivals"], mix["rate"], n, pool)
        prompts, outputs, gaps = run.permutation(prompts), run.permutation(outputs), run.permutation(gaps)
        # n arrivals spread over exactly this segment: the first at its
        # start, the last one gap before its end
        due = start + length * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
        for p, m, t in zip(prompts, outputs, due):
            toks = run.integers(0, vocab, int(p)).tolist()
            out.append(Req(len(out), seg, float(t), toks, int(m)))
    return out
