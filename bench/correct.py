"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests that finished in the window, drawn from the seed and always
holding the one with the most served tokens, is run through the plain
float32 reference (``bench/reference/<family>.py``) over its prompt and
its served tokens, teacher-forced, in one pass per request.  For each
served token the gap is how far the reference's logit of that token lies
below the reference's best logit at that position.  Two numbers of the
sample are compared with the mix's limits (``check``):

- ``mean_gap``, the gap averaged over every served token, against
  ``mean_gap_limit``, set between the readings of sound runs and those of
  the control (the same reference computed with int8 matmuls, in the
  program's place).  A token flips only where the reference's two best
  logits lie closer than the logit error, and then by about that error,
  so the mean grows with the square of the error and separates the
  control from the program several times over;
- ``widest_gap``, the largest single gap, against ``widest_gap_limit``,
  set between sound runs and the planted faults (``bench/faults.py``): it
  catches one badly wrong token that a mean would average away.  It grows
  only linearly with the error and swings as a maximum does, so it does
  not separate the control.

A request that finished with another number of tokens than it asked for,
or a token outside the vocabulary, says the wrong thing outright.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

__all__ = ["sample", "gaps", "check_served"]

Served = Tuple[List[int], List[int], int]  # prompt, served tokens, max_new


def sample(served: Sequence[Served], k: int, seed: int) -> List[Served]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: len(served[i][1]))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([int(seed) % 2**64, 7])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) if rest and k > 1 else []
    return [served[i] for i in [longest] + sorted(pick)]


def _ref_length(mix: Dict[str, Any]) -> int:
    """One padded length for every sequence, so the reference compiles once
    (causal, so padding after a sequence changes none of its logits)."""
    n = max(mix["prompt"]["buckets"]) + int(mix["output"]["max"])
    return -(-n // 128) * 128


def gaps(ctx, picked: Sequence[Served], quants: Sequence[str] = ("f32",)) -> Dict[str, List[float]]:
    """Per served token, the gap below the f32 reference's best logit of:
    the served token (``"served"``), and, for each other precision named
    in ``quants``, the token that precision puts first (the control)."""
    import jax.numpy as jnp

    from .reference.run import forward
    from .weights import make_params

    params = make_params(ctx.arch, ctx.cfg["init"], ctx.seed, dtype=jnp.float32)
    family, arch = ctx.cfg["reference"], ctx.arch_dict
    length = _ref_length(ctx.mix)
    out: Dict[str, List[float]] = {"served": []}
    for q in quants:
        if q != "f32":
            out[q] = []
    for prompt, toks, _ in picked:
        seq = np.zeros(length, np.int32)
        full = list(prompt) + list(toks[:-1])
        seq[: len(full)] = full
        rows = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
        ref = forward(family, arch, params, seq)[rows]
        best = ref.max(axis=-1)
        out["served"] += (best - ref[np.arange(len(toks)), toks]).tolist()
        for q in out:
            if q == "served":
                continue
            low = forward(family, arch, params, seq, quant=q)[rows]
            out[q] += (best - ref[np.arange(len(toks)), low.argmax(axis=-1)]).tolist()
    del params
    gc.collect()
    return out


def _judge(chk: Dict[str, Any], g: Sequence[float], wrong_length: int, out_of_range: int) -> Dict[str, Any]:
    mean = sum(g) / len(g) if g else float("inf")
    widest = max(g) if g else float("inf")
    checks = [
        ["mean_gap", mean, chk["mean_gap_limit"]],
        ["widest_gap", widest, chk["widest_gap_limit"]],
        ["wrong_length", wrong_length, 0],
        ["out_of_vocab", out_of_range, 0],
    ]
    ok = all(v <= lim for _, v, lim in checks)
    return {"correct": ok, "checks": checks}


def check_served(ctx, served: Sequence[Served], controls: Sequence[str] = ()) -> Dict[str, Any]:
    """The verdict on what the window served.  For each precision named in
    ``controls``, the same verdict with that precision's reference put in
    the program's place (under ``"controls"``): the control of the check."""
    chk = ctx.mix["check"]
    wrong_length = sum(1 for _, toks, m in served if len(toks) != m)
    vocab = ctx.arch.vocab_size
    out_of_range = sum(1 for _, toks, _ in served for t in toks if not 0 <= t < vocab)
    t0 = time.monotonic()
    picked = sample(served, int(chk["requests"]), ctx.seed)
    g = gaps(ctx, picked, quants=("f32", *controls)) if picked else {}
    print(
        f"reference: {len(picked)} requests, {len(g.get('served', []))} served tokens, "
        f"{time.monotonic() - t0:.2f}s", file=sys.stderr,
    )
    out = _judge(chk, g.get("served", []), wrong_length, out_of_range)
    out["controls"] = {q: _judge(chk, g.get(q, []), wrong_length, out_of_range) for q in controls}
    return out
