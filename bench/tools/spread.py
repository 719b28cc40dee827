#!/usr/bin/env python3
"""Two sets of runs of one cell, and the spread that its bounds are set from.

    python bench/tools/spread.py --workload <cell> --seeds 1,2,3,4,5,6 --sets 2 \\
        --seconds 45 --out spread_<cell>.jsonl [--trace-seeds 7,8,9]

Each run is its own process (``bench/run.py``, as the check runs it); the
sets use the same seeds.  This process never touches JAX, so each child
has the chip to itself.  For each end-to-end metric it prints each set's
median and its spread, the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, and the
wider of the two, the spread of all runs together, and the mean of the
sets' spreads with each set's run farthest from its median left out;
every result line is appended to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one(workload: str, seed: int, seconds: float, trace: int):
    t = time.monotonic()
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=1500,
    )
    wall = time.monotonic() - t
    line = None
    for raw in reversed(p.stdout.splitlines()):
        try:
            line = json.loads(raw)
            break
        except ValueError:
            continue
    return {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall, "result": line,
            "stderr_tail": p.stderr[-1500:]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    with open(args.out, "a") as out:
        for k in range(args.sets):
            rows = []
            for s in seeds:
                r = one(args.workload, s, args.seconds, 0)
                r["set"] = k
                out.write(json.dumps(r) + "\n")
                out.flush()
                res = r["result"] or {}
                print(f"set={k} seed={s} rc={r['rc']} wall={r['wall_s']:.1f}s correct={res.get('correct')} "
                      f"failed={res.get('failed')}/{res.get('attempted')} "
                      + " ".join(f"{n}={m['value']:.6g}" for n, m in res.get("metrics", {}).items())
                      + " " + " ".join(f"{n}={c['value']:.6g}" for n, c in res.get("check", {}).items()),
                      flush=True)
                rows.append(res)
            sets.append(rows)
        for s in [int(x) for x in args.trace_seeds.split(",") if x]:
            r = one(args.workload, s, args.seconds, 1)
            out.write(json.dumps(r) + "\n")
            out.flush()
            res = r["result"] or {}
            print(f"trace seed={s} rc={r['rc']} wall={r['wall_s']:.1f}s correct={res.get('correct')} "
                  f"device={json.dumps(res.get('device'))} metrics={json.dumps(res.get('metrics'))} "
                  f"check={json.dumps(res.get('check'))}", flush=True)
            print(f"  breakdown={json.dumps(res.get('breakdown'))}", flush=True)
    names = sorted({n for rows in sets for r in rows for n in r.get("metrics", {})})
    for n in names:
        per, trimmed, every = [], [], []
        for rows in sets:
            vals = [r["metrics"][n]["value"] for r in rows if n in r.get("metrics", {})]
            every += vals
            if len(vals) >= 3:
                per.append((statistics.median(vals), spread(vals)))
                med = statistics.median(vals)
                far = max(range(len(vals)), key=lambda i: abs(vals[i] - med))
                trimmed.append(spread([v for i, v in enumerate(vals) if i != far]))
        if per:
            print(f"{n}: " + " ".join(f"set{i} median={m:.6g} spread={s:.4f}" for i, (m, s) in enumerate(per))
                  + f" widest={max(s for _, s in per):.4f} all_runs={spread(every):.4f}"
                  + f" trimmed_mean={statistics.mean(trimmed):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
