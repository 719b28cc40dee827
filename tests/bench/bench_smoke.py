"""Runs of a serving cell on the CPU at smoke size, for the tests: the
harness with its look for a chip skipped and the cell cut to what a test
run can hold (smoke widths, 4 slots, short prompts and answers)."""
import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def smoke_ctx(workload: str, seed: int, seconds: float = 2.0, trace: bool = False):
    from bench import run as R
    from repro.configs import get_smoke_config

    spec = R.load_spec()
    cell, _, cfg, mix = R.cell_parts(spec, workload)
    sm = get_smoke_config(cfg["registry"])
    cfg["arch"] = {f.name: getattr(sm, f.name) for f in dataclasses.fields(sm) if f.name != "source"}
    mix.update(slots=4, context=128, max_prefill=32, rate=6.0, ramp_seconds=0.5, tail_cap_seconds=20)
    mix["prompt"] = dict(mix["prompt"], buckets=[8, 16, 32])
    mix["output"] = dict(mix["output"], mean=10, max=24)
    mix["trace"] = {"start_s": 0.3, "seconds": 1.0}
    return spec, R.make_ctx(cell, cfg, mix, seed, seconds, trace, time.monotonic())
