"""Host time of the comm hand-off (``InferenceServer._comm_step``: drain
retries, progress, reap, dispatch) per decode step, from the benchmark's
``bench.comm`` spans in the traced slice.  Nothing runs inside those
spans that has a span of its own, so a span's time is its self time."""
from bench.trace import span_stats


def read(ctx):
    s = span_stats(ctx, "bench.comm")
    if not s or not s["steps"]:
        return None
    return s["total_ms"] / s["steps"]
