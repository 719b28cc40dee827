"""Device time of one batched decode program (``models.decode_step`` as
``DecodeCore`` compiled it), averaged over its executions in the traced
slice."""
from bench.trace import classify


def read(ctx):
    c = classify(ctx)
    if c is None or c["decode"] is None:
        return None
    d = c["programs"][c["decode"]]
    return 1e3 * d["device_s"] / d["runs"]
