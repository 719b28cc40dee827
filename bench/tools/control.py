#!/usr/bin/env python3
"""Readings that a serving cell's correctness limit is set from, and the
check's verdicts on its control and its faults.

    python bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 15 \
        [--fault token_altered|state_unchanged]

One process, one set-up.  For each seed: that seed's weights are put in
the server's place, a short window runs at the cell's own rate, and what
it served goes through ``correct.check_served``, the check a run makes,
with the int8 control beside it.  Printed per seed, as JSON: the verdict
and the widest gap of the served tokens below the f32 reference's best
(the program's reading, the lower end of the limit), and the verdict and
widest gap of the tokens that the reference computed with int8 matmuls
puts first (the control, the upper end).  With ``--fault`` the fault of
``bench/faults.py`` is planted in the server first, and only the
program's verdict is read.  Runs on the chip only, like ``bench/run.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--control", default="int8")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent]

    import jax

    from bench import correct, run as R
    from bench.drivers import serve
    from bench.faults import FAULTS
    from bench.weights import make_params
    from repro.launch.compile_cache import enable_compile_cache

    spec = R.load_spec()
    cell, _, cfg, mix = R.cell_parts(spec, args.workload)
    R.require_chips(int(cell["chips"]))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = R.make_ctx(cell, cfg, mix, seeds[0], args.seconds, False, T_PROCESS)
    server = serve._build(ctx)
    serve._warm(server, mix, ctx.arch.vocab_size)
    if args.fault:
        FAULTS[args.fault](server)
    controls = () if args.fault else (args.control,)
    rows = []
    for seed in seeds:
        sctx = R.make_ctx(cell, cfg, mix, seed, args.seconds, False, T_PROCESS)
        server.run_until_idle()
        server.params = server.core.params = None
        gc.collect()
        server.params = server.core.params = make_params(sctx.arch, cfg["init"], seed)
        w = serve.window(server, sctx)
        server.run_until_idle()
        params, server.params, server.core.params = server.params, None, None
        del params
        gc.collect()
        t = time.monotonic()
        res = correct.check_served(sctx, w["served"], controls=controls)
        row = {"seed": seed, "fault": args.fault or None, "due": w["due"], "finished": w["finished"],
               "correct": res["correct"], "check": {n: {"value": v, "limit": lim} for n, v, lim in res["checks"]},
               "controls": {q: {"correct": c["correct"], "mean_gap": c["checks"][0][1],
                                "widest_gap": c["checks"][1][1]} for q, c in res["controls"].items()},
               "reference_s": time.monotonic() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "fault": args.fault or None, "correct": [r["correct"] for r in rows]}
    for n in ("mean_gap", "widest_gap"):
        lo = max(r["check"][n]["value"] for r in rows)
        summary[n] = {"lower": lo}
        if controls:
            hi = min(r["controls"][args.control][n] for r in rows)
            summary[n].update(upper=hi, ratio=hi / lo if lo else None)
    if controls:
        summary["control_correct"] = [r["controls"][args.control]["correct"] for r in rows]
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
