"""Median host wall time of one admission (``DecodeCore.admit``: scratch
cache, prefill dispatch, splice, the first token's argmax and its sync),
from the benchmark's ``bench.admit`` spans in the traced slice."""
from bench.trace import span_stats


def read(ctx):
    s = span_stats(ctx, "bench.admit")
    return s["p50_ms"] if s and s["n"] else None
