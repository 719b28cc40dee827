"""Whole-prefill share of the chip's peak: 2 · N · prompt tokens (N the
weight elements a token meets, each use of a shared block counted) plus
the one output-head row per prefill, over the device time of the prefill
programs that ran in the traced slice, over the bf16 peak."""
from bench.roofline.peaks import peaks
from bench.roofline.work import prefill_flops
from bench.trace import classify


def read(ctx):
    c = classify(ctx)
    if c is None or not c["prefill_runs"]:
        return None
    flops = prefill_flops(ctx["arch"], c["prefill_tokens"], c["prefill_runs"])
    return 100.0 * flops / c["prefill_device_s"] / peaks(ctx["kind"])["bf16_flops"]
