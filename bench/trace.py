"""Reduction of a profiler trace to what the per-layer readers need.

``view_from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
with nothing but JAX (``ProfileData``) and keeps four things, as plain
lists that a test can also build by hand:

* ``ops``: device operations, ``[chip, name, start_ns, dur_ns]``, from each
  TPU plane's ``XLA Ops`` line, named by their HLO instruction without its
  number (``%fusion.12 = ...`` is ``fusion``); a loop or conditional holds
  the operations of its body, so these nest;
* ``modules``: compiled-program executions on the device,
  ``[chip, name, start_ns, dur_ns]``, from ``XLA Modules``; the name
  carries the program's fingerprint (``jit__lambda(9418...)``), which
  tells apart programs that one Python name compiled to;
* ``spans``: the benchmark's own host spans (``bench.*`` annotations),
  ``[name, start_ns, dur_ns]``;
* ``window``: ``[start_ns, end_ns]`` of the ``bench.traced_window`` span.

Device and host events share the profiler's clock, so an idle gap on the
device can be labelled with the host span that was open during it.

``python -m bench.trace <file.xplane.pb>`` prints the planes, lines and
the commonest event names: the first look at a trace, before code is
written against it.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import Counter, defaultdict
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "find_xplane",
    "view_from_xplane",
    "busy_intervals",
    "busy_s",
    "idle_gaps",
    "label_at",
    "breakdown",
    "program_counts",
    "self_times",
    "span_stats",
    "classify",
    "kernel_time",
]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.traced_window"
# device operation names of each kernel, as the TPU trace shows them
KERNELS = {"ssd": re.compile(r"^ssd_chunk_kernel")}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_OP = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)?(?:\s*=|$)")


def _op_name(hlo: str) -> str:
    m = _OP.match(hlo)
    return m.group(1) if m else hlo.split(" ")[0]


def _stats(ev) -> Dict[str, Any]:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def view_from_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[list] = []
    modules: List[list] = []
    spans: List[list] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                short = line.name == "XLA Ops"
                dest = ops if short else modules
                for ev in line.events:
                    name = _op_name(ev.name) if short else ev.name
                    dest.append([chip, name, float(ev.start_ns), float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    window = [win[0][1], win[0][1] + win[0][2]] if win else None
    return {"ops": ops, "modules": modules, "spans": spans, "window": window}


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def busy_intervals(view: Dict[str, Any], chip: int) -> List[Tuple[float, float]]:
    """Union of the intervals in which an operation ran on ``chip``,
    clipped to the traced window."""
    iv = sorted((o[2], o[2] + o[3]) for o in view["ops"] if o[0] == chip)
    merged: List[List[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    lo, hi = view["window"]
    return _clip([(a, b) for a, b in merged], lo, hi)


def chips(view: Dict[str, Any]) -> List[int]:
    return sorted({o[0] for o in view["ops"]})


def busy_s(view: Dict[str, Any]) -> float:
    """Seconds with an operation running, averaged over the chips traced."""
    cs = chips(view)
    if not cs:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(view, c)) for c in cs) / len(cs) / 1e9


def window_s(view: Dict[str, Any]) -> float:
    lo, hi = view["window"]
    return (hi - lo) / 1e9


def idle_gaps(view: Dict[str, Any], chip: int) -> List[Tuple[float, float]]:
    """The traced window's intervals with nothing running on ``chip``."""
    lo, hi = view["window"]
    gaps, t = [], lo
    for a, b in busy_intervals(view, chip):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_at(view: Dict[str, Any], t: float) -> str:
    """The innermost benchmark span open at ``t`` (the traced window's
    own span only where no other is open)."""
    best: Optional[list] = None
    for s in view["spans"]:
        if s[0] == WINDOW_SPAN or not (s[1] <= t <= s[1] + s[2]):
            continue
        if best is None or s[2] < best[2]:
            best = s
    return best[0] if best else "host.outside_spans"


def self_times(view: Dict[str, Any], chip: int) -> Counter:
    """Nanoseconds of each operation name on ``chip`` in the traced window,
    less the time of the operations nested inside it (a loop's body is
    the body's, not the loop's)."""
    lo, hi = view["window"]
    evs = sorted((o for o in view["ops"] if o[0] == chip), key=lambda o: (o[2], -o[3]))
    out: Counter = Counter()
    stack: List[list] = []  # [end, name, own]
    for _, name, st, dur in evs:
        while stack and stack[-1][0] <= st:
            end, n, own = stack.pop()
            out[n] += own
        a, b = max(st, lo), min(st + dur, hi)
        own = max(b - a, 0.0)
        if stack:
            stack[-1][2] -= own
        stack.append([st + dur, name, own])
    for _, n, own in stack:
        out[n] += own
    return out


def breakdown(view: Dict[str, Any], top: int = 10) -> Dict[str, list]:
    """Device operations that took most time (self time) and the idle
    time by what the host was doing, both averaged over the chips traced
    (seconds)."""
    cs = chips(view) or [0]
    by_op: Counter = Counter()
    for c in cs:
        for n, ns in self_times(view, c).items():
            by_op[n] += ns / 1e9 / len(cs)
    by_gap: Counter = Counter()
    for c in cs:
        for a, b in idle_gaps(view, c):
            by_gap[label_at(view, (a + b) / 2)] += (b - a) / 1e9 / len(cs)
    return {
        "device_ops": [[n, s] for n, s in by_op.most_common(top)],
        "idle_gaps": [[n, s] for n, s in by_gap.most_common(top)],
    }


def program_counts(view: Dict[str, Any], chip: int = 0) -> Dict[str, Dict[str, Any]]:
    """Per compiled program on ``chip``: executions that began in the
    traced window, and their device time."""
    lo, hi = view["window"]
    out: Dict[str, Dict[str, Any]] = defaultdict(lambda: {"runs": 0, "device_s": 0.0})
    for c, name, st, dur in view["modules"]:
        if c != chip or not (lo <= st < hi):
            continue
        rec = out[name]
        rec["runs"], rec["device_s"] = rec["runs"] + 1, rec["device_s"] + dur / 1e9
    return dict(out)


def _in_window(view, spans):
    lo, hi = view["window"]
    return [x for x in spans if lo <= x[1] and x[1] + x[2] <= hi]


def span_stats(ctx: Dict[str, Any], name: str) -> Optional[Dict[str, float]]:
    """Count, total and median (ms) of the spans called ``name`` that lie
    in the traced slice, and the decode steps taken during it."""
    view = ctx.get("view")
    if not view or not view["window"]:
        return None
    durs = sorted(x[2] / 1e6 for x in _in_window(view, [s for s in view["spans"] if s[0] == name]))
    m = ctx["marks"]
    steps = m["trace_close"]["steps"] - m["trace_open"]["steps"]
    if not durs:
        return {"n": 0, "total_ms": 0.0, "p50_ms": float("nan"), "steps": steps}
    n = len(durs)
    p50 = durs[n // 2] if n % 2 else (durs[n // 2 - 1] + durs[n // 2]) / 2
    return {"n": n, "total_ms": sum(durs), "p50_ms": p50, "steps": steps}


def _admissions(ctx: Dict[str, Any]) -> List[Tuple[float, float, int]]:
    """(start_ns, end_ns, prompt length) of each admission in the traced
    slice: its ``bench.admit`` span, matched in order to the driver's log
    (no admission is open when the trace starts or stops: both happen
    between engine steps)."""
    view, m = ctx["view"], ctx["marks"]
    spans = sorted(_in_window(view, [s for s in view["spans"] if s[0] == "bench.admit"]), key=lambda s: s[1])
    first = m["trace_open"]["prefill_calls"] - ctx["admit_base"]
    log = ctx["admits"][first : first + len(spans)]
    return [(s[1], s[1] + s[2], a[2]) for s, a in zip(spans, log)]


def classify(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Which compiled program is the decode step (of those whose executions
    in the slice match the decode steps counted there, the one with the
    most device time) and which ran
    inside admissions and hold the SSD kernel (the prefills, which every
    model here runs through it), with their prompt tokens."""
    view = ctx.get("view")
    if not view or not view["window"]:
        return None
    progs = program_counts(view, 0)
    m = ctx["marks"]
    steps = m["trace_close"]["steps"] - m["trace_open"]["steps"]
    # several programs run once per step (the argmax too), and the slice's
    # edges can cut one execution off: of those within one run of the
    # step count, the decode program is the one with the most device time
    per_step = [p for p in progs if steps > 0 and abs(progs[p]["runs"] - steps) <= max(1, steps // 10)]
    decode = max(per_step, key=lambda p: progs[p]["device_s"]) if per_step else None
    runs, dev, tokens = 0, 0.0, 0
    for st, dur, length in _prefills(ctx):
        runs, dev, tokens = runs + 1, dev + dur / 1e9, tokens + length
    return {"programs": progs, "decode": decode, "prefill_runs": runs,
            "prefill_device_s": dev, "prefill_tokens": tokens}


def _prefills(ctx: Dict[str, Any]) -> List[Tuple[float, float, int]]:
    """(start_ns, dur_ns, prompt length) of each program execution on chip
    0 that ran inside an admission of the slice and holds the SSD kernel."""
    view = ctx["view"]
    ssd = sorted(o[2] for o in view["ops"] if o[0] == 0 and KERNELS["ssd"].search(o[1]))
    out = []
    for a0, a1, length in _admissions(ctx):
        for c, name, st, dur in view["modules"]:
            if c == 0 and a0 <= st and st + dur <= a1 and _any_in(ssd, st, st + dur):
                out.append((st, dur, length))
    return out


def _any_in(sorted_starts: List[float], lo: float, hi: float) -> bool:
    import bisect

    i = bisect.bisect_left(sorted_starts, lo)
    return i < len(sorted_starts) and sorted_starts[i] < hi


def kernel_time(ctx: Dict[str, Any], kernel: str) -> Optional[Dict[str, Any]]:
    """Device time of ``kernel``'s operations inside the prefills of the
    traced slice, and how many prefills of each prompt length ran it."""
    view = ctx.get("view")
    if not view or not view["window"]:
        return None
    pat = KERNELS[kernel]
    kops = sorted((o[2], o[3]) for o in view["ops"] if o[0] == 0 and pat.search(o[1]))
    dev, lengths = 0.0, Counter()
    for st, dur, length in _prefills(ctx):
        t = sum(d for s0, d in kops if st <= s0 and s0 + d <= st + dur)
        if t > 0:
            dev += t / 1e9
            lengths[length] += 1
    if not lengths:
        return None
    return {"device_s": dev, "lengths": dict(lengths)}


def _describe(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs)
            t0 = min((e.start_ns for e in evs), default=0)
            t1 = max((e.start_ns + e.duration_ns for e in evs), default=0)
            print(f"  LINE {line.name!r} events={len(evs)} span_ns=[{t0:.0f}, {t1:.0f}]")
            for n, k in names.most_common(12):
                ex = next(e for e in evs if e.name == n)
                print(f"    {k:6d} {n[:90]!r} stats={str(_stats(ex))[:300]}")


if __name__ == "__main__":
    _describe(sys.argv[1])
