#!/usr/bin/env python3
"""What the serving loop's own spans and counters cost with the profiler off.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/span_cost.py [--repeats 20000]

Serves a few requests on a smoke-size model under a profiler session and
reads back the program's ``serve.*`` spans with their arguments.  Then,
with the profiler off, it replays each recorded span as the program opens
it (``TraceAnnotation(name, **args)``) and the jit-cache look of
``DecodeCore._note_compiles``, and prints the cost per decode turn (every
span but an admission's, over the decode steps) and per admission
(``serve.admit`` and its children).  A timing of host code on the CPU it
runs on: it says what the instrumentation adds to a loop turn, nothing
about the device.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5], [2, 7, 1], [8, 2, 8, 1]]


def record():
    import jax

    from bench.serve_trace import view_from_xplane
    from bench.trace import find_xplane
    from repro.configs import SMOKES
    from repro.models import init_params
    from repro.serve import InferenceServer, ServeConfig

    arch = SMOKES["tinyllama-1.1b"].variant(dtype="float32")
    server = InferenceServer(arch, init_params(jax.random.PRNGKey(0), arch), ServeConfig(slots=4, context=64))
    for p in PROMPTS:  # compile every shape outside the session
        server.submit(p, max_new=2)
    server.run_until_idle()
    steps0, admits0 = server.core.steps, server.core.prefill_calls
    with tempfile.TemporaryDirectory(prefix="span-cost-") as d:
        jax.profiler.start_trace(d)
        try:
            for p in PROMPTS:
                server.submit(p, max_new=12)
            server.run_until_idle()
        finally:
            jax.profiler.stop_trace()
        spans = [s for s in view_from_xplane(find_xplane(d))["spans"] if s[0].startswith("serve.")]
    return server.core, spans, server.core.steps - steps0, server.core.prefill_calls - admits0


def per_call_us(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(repeats):
            fn()
        best = min(best, (time.perf_counter() - t) / repeats)
    return best * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--repeats", type=int, default=20000)
    args = ap.parse_args()
    from jax.profiler import TraceAnnotation

    core, spans, steps, admits = record()

    def replay(rows):
        def go():
            for name, _, _, kw in rows:
                with TraceAnnotation(name, **kw):
                    pass

        return per_call_us(go, max(1, args.repeats // max(1, len(rows))))

    admission = [s for s in spans if s[0].startswith("serve.admit")]
    turn = [s for s in spans if s not in admission]
    note_us = per_call_us(core._note_compiles, args.repeats)
    turn_us = replay(turn) / steps + note_us
    admit_us = replay(admission) / admits + note_us
    print(f"recorded: {steps} decode steps, {admits} admissions; spans per step "
          f"{len(turn) / steps:.2f}, per admission {len(admission) / admits:.2f}")
    print(f"by name: {dict(Counter(s[0] for s in spans))}")
    print(f"profiler off: _note_compiles {note_us:.3f} us; per decode turn {turn_us:.2f} us; "
          f"per admission {admit_us:.2f} us")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
