#!/usr/bin/env python3
"""Run one benchmark cell once and print one JSON result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``); the mix names its driver
(``bench/drivers/<driver>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, the
device's busy time and a breakdown, read from a profiler trace of a slice
of the window.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result: it never measures the CPU.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse
import importlib
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_parts(spec: Dict[str, Any], workload: str, root: Path = ROOT):
    """(cell, configuration entry, configuration file, traffic mix)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(root / conf["file"]) as f:
        cfg = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)
    return cell, conf, cfg, mix


def metrics_for(spec: Dict[str, Any], workload: str, section: str) -> List[Dict[str, Any]]:
    return [m for m in spec[section] if workload in m.get("workloads", [workload])]


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {devs[0].platform!r}); refusing to measure")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} TPU chips, JAX found {len(devs)}")
    return devs


def make_ctx(cell, cfg, mix, seed: int, seconds: float, trace: bool, t_process: float):
    from repro.configs.base import ArchConfig

    arch = ArchConfig(**cfg["arch"])
    return SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, seed=int(seed), seconds=seconds, trace=trace,
        arch=arch, arch_dict=dict(cfg["arch"]), t_process=t_process,
    )


def read_layer_metrics(spec, workload: str, layer_ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics_for(spec, workload, "per_layer"):
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(layer_ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(spec, workload: str, res: Dict[str, Any], trace: bool, devices) -> Dict[str, Any]:
    from bench import trace as tr

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line: Dict[str, Any] = {"correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        view = res["layer_ctx"]["view"]
        line["metrics"] = read_layer_metrics(spec, workload, res["layer_ctx"])
        device["busy_s"] = tr.busy_s(view)
        device["window_s"] = tr.window_s(view)
        line["device"] = device
        line["breakdown"] = tr.breakdown(view)
    else:
        line["metrics"] = {
            m["name"]: {"value": res["e2e"][m["name"]], "unit": m["unit"]}
            for m in metrics_for(spec, workload, "end_to_end")
        }
        line["device"] = device
    line["check"] = {name: {"value": v, "limit": lim} for name, v, lim in res["checks"]}
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the package, not this directory, is what the imports below resolve
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if Path(p or ".").resolve() != BENCH]
    spec = load_spec()
    cell, _, cfg, mix = cell_parts(spec, args.workload)
    devices = require_chips(int(cell["chips"]))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # cache every program, however quick to compile: set-up stays steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = make_ctx(cell, cfg, mix, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    res = driver.run(ctx)
    line = result_line(spec, args.workload, res, bool(args.trace), devices[: int(cell["chips"])])
    for name, c in line["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
