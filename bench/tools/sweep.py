#!/usr/bin/env python3
"""Find a serving cell's knee: one set-up, then a window at each rate.

    python bench/tools/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 3,4,5,6

For each rate it prints the requests due and finished, the backlog left
when the window's requests were done or the cap was reached, the tails
and the tokens per second.  The rates run from the lowest up, and the
sweep stops after the first whose window's requests did not all finish
before the cap: the knee is the highest rate below it.  With
``--keep-trace DIR`` a traced window at the mix's own rate comes first,
and its ``.xplane.pb`` and a description of it are left in ``DIR``.
With ``--gaps 1`` the last window's sample is compared with the
reference and with the int8 control, and both widest gaps are printed.
Runs on the chip only, like ``bench/run.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import contextlib
import io
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", default="")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--gaps", type=int, default=0)
    args = ap.parse_args()
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent]

    import jax

    from bench import correct, run as R, trace as tr
    from bench.drivers import serve
    from repro.launch.compile_cache import enable_compile_cache

    spec = R.load_spec()
    cell, _, cfg, mix = R.cell_parts(spec, args.workload)
    R.require_chips(int(cell["chips"]))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = R.make_ctx(cell, cfg, mix, args.seed, args.seconds, False, T_PROCESS)
    server = serve._build(ctx)
    t = time.monotonic()
    serve._warm(server, mix, ctx.arch.vocab_size)
    print(f"set-up: build {t - T_PROCESS:.2f}s warm {time.monotonic() - t:.2f}s", flush=True)

    if args.keep_trace:
        os.makedirs(args.keep_trace, exist_ok=True)
        tctx = R.make_ctx(cell, cfg, mix, args.seed + 1, min(args.seconds, 12), True, T_PROCESS)
        tctx.keep_trace = args.keep_trace
        w = serve.window(server, tctx)
        w["layer_ctx"]["kind"] = jax.devices()[0].device_kind
        lay = R.read_layer_metrics(spec, args.workload, w["layer_ctx"])
        view = w["layer_ctx"]["view"]
        print("traced:", json.dumps({"metrics": lay, "busy_s": tr.busy_s(view), "window_s": tr.window_s(view),
                                     "breakdown": tr.breakdown(view)}), flush=True)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            tr._describe(tr.find_xplane(args.keep_trace))
        with open(os.path.join(args.keep_trace, "describe.txt"), "w") as f:
            f.write(buf.getvalue())

    last = None
    for r in sorted(float(x) for x in args.rates.split(",") if x):
        server.run_until_idle(max_steps=100_000)  # no backlog carried in
        rctx = R.make_ctx(cell, cfg, dict(mix, rate=r), args.seed, args.seconds, False, T_PROCESS)
        w = serve.window(server, rctx)
        e = w["e2e"]
        print(f"rate={r:g} due={w['due']} finished={w['finished']} backlog_end={w['backlog_end']} "
              f"ttft_p50_ms={e['ttft_p50_ms']:.1f} ttft_p95_ms={e['ttft_p95_ms']:.1f} tbt_p95_ms={e['tbt_p95_ms']:.2f} "
              f"out_tok_s={e['out_tok_s']:.1f} generator_lateness_max_s={w['lateness_max_s']:.3f}", flush=True)
        if w["served"]:
            last = (rctx, w)
        if w["finished"] < w["due"]:
            break  # past the knee: the window's own requests did not all finish
    d = jax.devices()[0]
    print(f"memory_peak_bytes={(d.memory_stats() or {}).get('peak_bytes_in_use')}", flush=True)
    if args.gaps and last:
        rctx, w = last
        del server
        t = time.monotonic()
        picked = correct.sample(w["served"], int(mix["check"]["requests"]), args.seed)
        g = correct.gaps(rctx, picked, quants=("f32", "int8"))
        print(f"gaps: tokens={len(g['served'])} served_widest={max(g['served']):.5f} "
              f"int8_widest={max(g['int8']):.5f} ({time.monotonic() - t:.1f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
