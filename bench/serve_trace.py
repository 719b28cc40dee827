"""The serving loop's own spans in a profiler trace, beside the benchmark's.

``bench.trace`` keeps the benchmark's ``bench.*`` host spans only.  This
module also reads the program's ``serve.*`` spans (``repro.serve.server``),
each row ``[name, start_ns, dur_ns, args]`` with the arguments the span
carries, and reduces a traced slice to what they show:

* ``split``: per span name, count, median and total wall time, and the
  device's busy time inside it;
* ``numbers``: ``queue_wait_ms_p95`` (p95 of ``serve.admit``'s
  ``queued_ns``), ``admit_sync_ms_p50`` (median ``serve.admit.first_token``),
  ``decode_host_ms_per_step`` (``serve.decode`` less its
  ``serve.decode.sync``, plus ``serve.flush``, per decode step) and
  ``host_syncs_per_step`` (first-token and decode reads per decode step).

``bench.trace.breakdown`` on this view names each idle gap by the
innermost span of either family.  The module can go once ``bench.trace``
keeps the ``serve.*`` spans itself.

    python -m bench.serve_trace <file.xplane.pb>

prints all of it for a trace that ``bench/tools/sweep.py --keep-trace`` or
``bench/tools/loop_counts.py --keep-trace`` left.
"""
from __future__ import annotations

import json
import statistics
import sys
from typing import Any, Dict, List, Optional

from . import trace as tr

__all__ = ["view_from_xplane", "in_slice", "split", "numbers"]


def view_from_xplane(path: str) -> Dict[str, Any]:
    """``bench.trace.view_from_xplane``'s view, with the ``serve.*`` spans
    and their arguments added to its ``spans``."""
    from jax.profiler import ProfileData

    view = tr.view_from_xplane(path)
    spans = list(view["spans"])
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append([ev.name, float(ev.start_ns), float(ev.duration_ns), dict(ev.stats)])
    return dict(view, spans=spans)


def in_slice(view: Dict[str, Any], name: str) -> List[list]:
    """The spans called ``name`` that lie in the traced slice."""
    lo, hi = view["window"]
    return [s for s in view["spans"] if s[0] == name and lo <= s[1] and s[1] + s[2] <= hi]


def split(view: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Per ``serve.*`` span name in the slice: count, median and total
    wall time, and chip 0's busy time inside the spans (ms)."""
    busy = tr.busy_intervals(view, 0) if tr.chips(view) else []
    names = sorted({s[0] for s in view["spans"] if s[0].startswith("serve.")})
    out = {}
    for n in names:
        rows = in_slice(view, n)
        if not rows:
            continue
        durs = [s[2] / 1e6 for s in rows]
        inside = sum(max(0.0, min(e, s[1] + s[2]) - max(b, s[1])) for s in rows for b, e in busy
                     if e > s[1] and b < s[1] + s[2])
        out[n] = {"n": len(rows), "p50_ms": statistics.median(durs), "total_ms": sum(durs),
                  "busy_ms": inside / 1e6}
    return out


def numbers(view: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """The four per-layer numbers the program's spans give, or None where
    the slice holds none of the spans they read."""
    import numpy as np

    def total(name):
        return sum(s[2] for s in in_slice(view, name)) / 1e6

    waits = [s[3]["queued_ns"] / 1e6 for s in in_slice(view, "serve.admit") if "queued_ns" in s[3]]
    first = [s[2] / 1e6 for s in in_slice(view, "serve.admit.first_token")]
    steps = len(in_slice(view, "serve.decode"))
    syncs = len(first) + len(in_slice(view, "serve.decode.sync"))
    return {
        "queue_wait_ms_p95": float(np.percentile(waits, 95)) if waits else None,
        "admit_sync_ms_p50": statistics.median(first) if first else None,
        "decode_host_ms_per_step": (
            (total("serve.decode") - total("serve.decode.sync") + total("serve.flush")) / steps if steps else None
        ),
        "host_syncs_per_step": syncs / steps if steps else None,
    }


def _main(path: str) -> None:
    view = view_from_xplane(path)
    print(f"busy_s={tr.busy_s(view):.6f} window_s={tr.window_s(view):.6f}")
    for n, r in split(view).items():
        print(f"{n:26s} n={r['n']:5d} p50={r['p50_ms']:9.4f} total={r['total_ms']:10.3f} "
              f"busy_inside={r['busy_ms']:10.3f} ms")
    # the innermost span of either family open at each gap's midpoint
    print("idle_gaps", json.dumps(tr.breakdown(view, top=20)["idle_gaps"]))
    print("numbers", json.dumps(numbers(view)))


if __name__ == "__main__":
    _main(sys.argv[1])
