"""Share of decode-batch rows that carried a live request over the
measured window: decode tokens / (decode steps x slots), from the
program's counters (``DecodeCore.steps``, ``tokens_out``,
``prefill_calls``; each prefill emits one token outside the decode)."""


def read(ctx):
    m = ctx.get("marks", {})
    if "open" not in m or "close" not in m:
        return None
    a, b = m["open"], m["close"]
    steps = b["steps"] - a["steps"]
    decoded = (b["tokens_out"] - a["tokens_out"]) - (b["prefill_calls"] - a["prefill_calls"])
    if steps <= 0:
        return None
    return 100.0 * decoded / (steps * ctx["slots"])
