#!/usr/bin/env python3
"""The serving loop's pace over a window of a cell, untraced and traced.

    python bench/tools/loop_counts.py --workload <cell> --seed <n> [--seconds 51] [--keep-trace FILE]

One set-up, then two windows of the cell's mix on the same seed: the first
untraced, as the benchmark takes its end-to-end metrics, the second traced,
as it takes its per-layer metrics.  For each it prints the window's tails
and, from the program's counters at the driver's marks, the decode turns,
the admissions a turn, the turn's length and the batch occupancy: over
the whole window, and for the traced one over its traced slice too.  It
also prints what ``DecodeCore.host_syncs`` and ``DecodeCore.compiles`` grew
by over each window's whole run (its ramp and drain too).  With
``--keep-trace`` the traced slice's ``.xplane.pb`` is left in FILE for
``python -m bench.serve_trace``.  Runs on the chip only, like
``bench/run.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def pace(a, b, wall_s: float, slots: int) -> str:
    """Turns, admissions and occupancy between two marks ``wall_s`` apart."""
    steps = b["steps"] - a["steps"]
    admits = b["prefill_calls"] - a["prefill_calls"]
    decoded = b["tokens_out"] - a["tokens_out"] - admits
    if steps <= 0:
        return f"turns=0 admissions={admits}"
    return (f"turns={steps} admissions={admits} admissions_per_turn={admits / steps:.4f} "
            f"turn_ms={wall_s * 1e3 / steps:.4f} occupancy={100 * decoded / (steps * slots):.2f}%")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args()
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent]

    import jax

    from bench import run as R, trace as tr
    from bench.drivers import serve
    from repro.launch.compile_cache import enable_compile_cache

    spec = R.load_spec()
    cell, _, cfg, mix = R.cell_parts(spec, args.workload)
    R.require_chips(int(cell["chips"]))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = R.make_ctx(cell, cfg, mix, args.seed, args.seconds, False, T_PROCESS)
    server = serve._build(ctx)
    serve._warm(server, mix, ctx.arch.vocab_size)
    core, slots = server.core, int(mix["slots"])

    for trace in (False, True):
        server.run_until_idle(max_steps=100_000)  # no backlog carried in
        wctx = R.make_ctx(cell, cfg, mix, args.seed, args.seconds, trace, T_PROCESS)
        if trace and args.keep_trace:
            wctx.keep_trace = args.keep_trace
        syncs, compiles, steps = core.host_syncs, core.compiles, core.steps
        w = serve.window(server, wctx)
        run_steps = core.steps - steps
        e, m = w["e2e"], w["layer_ctx"]["marks"]
        tag = "traced" if trace else "untraced"
        print(f"{tag}: ttft_p95_ms={e['ttft_p95_ms']:.4f} tbt_p95_ms={e['tbt_p95_ms']:.4f} "
              f"out_tok_s={e['out_tok_s']:.4f} finished={w['finished']}/{w['due']}", flush=True)
        print(f"{tag} window: {pace(m['open'], m['close'], args.seconds, slots)}", flush=True)
        if trace:
            view = w["layer_ctx"]["view"]
            print(f"{tag} slice: {pace(m['trace_open'], m['trace_close'], tr.window_s(view), slots)}", flush=True)
        print(f"{tag} run: host_syncs={core.host_syncs - syncs} compiles={core.compiles - compiles} "
              f"host_syncs_per_turn={(core.host_syncs - syncs) / max(1, run_steps):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
