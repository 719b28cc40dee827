"""Open-loop serving driver: one window of a traffic mix against
``repro.launch.serve.build_server`` (``InferenceServer`` over its comm
hand-off).

A generator thread submits each request through ``server.submit`` when it
is due; the main thread drives ``server.step()`` and, after every step,
stamps each new token of each request it follows with the step's end,
which is when the token batch reached the client side.  Time to first
token is taken from when the request was due, not from when it was
submitted, so a late generator or a stalled loop is charged.
"""
from __future__ import annotations

import gc
import statistics
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import correct
from ..workload import schedule

__all__ = ["run"]


def _p95(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if xs else float("nan")


class _Follow:
    """Client-side record of one request: its tokens' arrival stamps."""

    __slots__ = ("spec", "req", "stamps")

    def __init__(self, spec, req):
        self.spec, self.req, self.stamps = spec, req, []


def _build(ctx):
    from repro.launch.serve import build_server

    from ..weights import make_params

    mix = ctx.mix
    params = make_params(ctx.arch, ctx.cfg["init"], ctx.seed)
    server = build_server(
        ctx.arch, params, slots=mix["slots"], context=mix["context"],
        max_prefill=mix["max_prefill"], transport=mix.get("transport", "collective"),
    )
    return server


def _warm(server, mix, vocab: int) -> None:
    """Compile every shape the window uses: one prefill per prompt bucket,
    and with them the decode step, the cache splice and the argmaxes."""
    rng = np.random.default_rng(0)
    for b in sorted(mix["prompt"]["buckets"]):
        r = server.submit(rng.integers(0, vocab, b).tolist(), max_new=2)
        while not r.done_event.is_set():
            server.step()
    server.run_until_idle()


def _install_spans(server, log: Dict[str, list]):
    """Host spans around the calls into each layer, on this instance only."""
    import jax

    def wrap(obj, attr, name, record=None):
        orig = getattr(obj, attr)

        def wrapped(*a, **k):
            t0 = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(name):
                out = orig(*a, **k)
            if record is not None:
                record(t0, time.perf_counter_ns(), a)
            return out

        setattr(obj, attr, wrapped)

    wrap(server, "_comm_step", "bench.comm")
    wrap(server.core, "step", "bench.decode")
    wrap(server.core, "admit", "bench.admit", lambda t0, t1, a: log["admits"].append((t0, t1, len(a[0].prompt))))


def run(ctx) -> Dict[str, Any]:
    """Set-up, one window, then the check against the reference."""
    import jax

    server = _build(ctx)
    _warm(server, ctx.mix, ctx.arch.vocab_size)
    w = window(server, ctx)
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    w["e2e"]["setup_s"] = w["t0"] - ctx.t_process
    w["layer_ctx"]["kind"] = dev.device_kind
    # correctness, once the program's state is freed
    del server
    gc.collect()
    check = correct.check_served(ctx, w["served"])
    return {
        "attempted": w["attempted"],
        "failed": w["failed"],
        "correct": check["correct"],
        "checks": check["checks"],
        "e2e": w["e2e"],
        "layer_ctx": w["layer_ctx"],
        "memory_peak_bytes": peak,
    }


def window(server, ctx) -> Dict[str, Any]:
    """The ramp, the measured window and its drain, at ``ctx.mix``'s rate."""
    import jax

    mix, seconds = ctx.mix, float(ctx.seconds)
    vocab = ctx.arch.vocab_size
    core = server.core
    reqs = schedule(mix, seconds, ctx.seed, vocab)
    log: Dict[str, Any] = {"admits": [], "admit_base": core.prefill_calls}
    if ctx.trace:
        _install_spans(server, log)

    follows: List[_Follow] = []
    live: List[_Follow] = []
    live_lock = threading.Lock()
    lateness: List[float] = []
    stop = threading.Event()
    ramp = float(mix["ramp_seconds"])
    t_ramp = time.monotonic()
    t0 = t_ramp + ramp  # the window opens here; set-up ends here

    def generator():
        for spec in reqs:
            due = t0 + spec.due
            while not stop.is_set():
                dt = due - time.monotonic()
                if dt <= 0:
                    break
                time.sleep(min(dt, 0.05))
            if stop.is_set():
                return
            req = server.submit(spec.prompt, max_new=spec.max_new)
            lateness.append(time.monotonic() - due)
            f = _Follow(spec, req)
            with live_lock:
                follows.append(f)
                live.append(f)

    gen = threading.Thread(target=generator, name="bench-generator", daemon=True)
    gen.start()

    window_reqs = [s for s in reqs if s.segment == "window"]
    end = t0 + seconds
    cap = end + float(mix["tail_cap_seconds"])
    marks: Dict[str, Dict[str, int]] = {}
    trace_cfg = mix.get("trace", {})
    t_trace0 = t0 + float(trace_cfg.get("start_s", 2.0))
    t_trace1 = t_trace0 + float(trace_cfg.get("seconds", 3.0))
    tracing = None
    trace_dir: Optional[str] = None
    window_ann = None

    def counters() -> Dict[str, int]:
        return {"steps": core.steps, "tokens_out": core.tokens_out, "prefill_calls": core.prefill_calls}

    window_done = 0
    while True:
        stepped = server.step()
        now = time.monotonic()
        with live_lock:
            cur = list(live)
        done = []
        for f in cur:
            n = len(f.req.out_tokens)
            if n > len(f.stamps):
                f.stamps.extend([now] * (n - len(f.stamps)))
            if f.req.done_event.is_set():
                done.append(f)
        if done:
            window_done += sum(1 for f in done if f.spec.segment == "window")
            with live_lock:
                live[:] = [f for f in live if not f.req.done_event.is_set()]
        if "open" not in marks and now >= t0:
            marks["open"] = counters()
        if "close" not in marks and now >= end:
            marks["close"] = counters()
        if ctx.trace:
            if tracing is None and now >= t_trace0:
                import tempfile

                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                # device and host events only: the Python tracer would
                # slow every call it sees, the host path most of all
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                window_ann = jax.profiler.TraceAnnotation("bench.traced_window")
                window_ann.__enter__()
                marks["trace_open"] = counters()
                tracing = True
            elif tracing and now >= t_trace1:
                window_ann.__exit__(None, None, None)
                marks["trace_close"] = counters()
                jax.profiler.stop_trace()
                tracing = False
        if now >= end and (window_done == len(window_reqs) or now >= cap):
            break
        if not stepped:
            time.sleep(2e-4)
    stop.set()
    gen.join()
    t_done = time.monotonic()

    # ---------------------------------------------------------- the window
    win = [f for f in follows if f.spec.segment == "window"]
    finished = [f for f in win if f.req.done_event.is_set()]
    ttft = [f.stamps[0] - (t0 + f.spec.due) for f in win if f.stamps]
    tbt = [b - a for f in win for a, b in zip(f.stamps, f.stamps[1:])]
    out_tokens = sum(1 for f in follows for s in f.stamps if t0 <= s < end)
    unfinished = len(window_reqs) - len(finished)
    print(
        f"window: due={len(window_reqs)} finished={len(finished)} ramp={ramp:.1f}s "
        f"drain={t_done - end:.2f}s generator lateness p50={statistics.median(lateness) * 1e3:.3f}ms "
        f"p99={float(np.percentile(lateness, 99)) * 1e3:.3f}ms max={max(lateness) * 1e3:.3f}ms",
        file=sys.stderr,
    )
    e2e = {
        "ttft_p50_ms": float(np.median(ttft)) * 1e3 if ttft else float("nan"),
        "ttft_p95_ms": _p95(ttft) * 1e3,
        "tbt_p95_ms": _p95(tbt) * 1e3,
        "out_tok_s": out_tokens / seconds,
    }
    layer_ctx = {
        "arch": ctx.arch_dict,
        "mix": mix,
        "marks": marks,
        "admits": log["admits"],
        "admit_base": log["admit_base"],
        "slots": mix["slots"],
    }
    view = None
    if ctx.trace and trace_dir:
        import shutil

        from .. import trace as tr

        if tracing:  # the window closed before the traced slice did
            window_ann.__exit__(None, None, None)
            marks["trace_close"] = counters()
            jax.profiler.stop_trace()
        xplane = tr.find_xplane(trace_dir)
        if getattr(ctx, "keep_trace", None):
            shutil.copy(xplane, ctx.keep_trace)
        view = tr.view_from_xplane(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
    layer_ctx["view"] = view
    return {
        "t0": t0,
        "attempted": len(window_reqs),
        "failed": unfinished,
        "e2e": e2e,
        "layer_ctx": layer_ctx,
        "served": [(f.spec.prompt, list(f.req.out_tokens), f.spec.max_new) for f in finished],
        "due": len(window_reqs),
        "finished": len(finished),
        "lateness_max_s": max(lateness) if lateness else 0.0,
        "backlog_end": sum(1 for f in follows if not f.req.done_event.is_set()),
    }
