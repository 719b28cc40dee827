"""Each per-layer reader, and the trace reduction under them, on a small
recorded trace view (``data/serve_view.json``) whose numbers are worked
out here by hand."""
import copy
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace as tr  # noqa: E402

PEAK_FLOPS, PEAK_BW = 197e12, 819e9


@pytest.fixture()
def ctx():
    with open(os.path.join(os.path.dirname(__file__), "data", "serve_view.json")) as f:
        c = json.load(f)
    c["admits"] = [tuple(a) for a in c["admits"]]
    return c


def read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def test_comm_ms_per_step(ctx):
    # 1 + 0.5 + 1 + 0.5 ms of comm spans over 6 - 2 = 4 decode steps
    assert read("comm_ms_per_step", ctx) == pytest.approx(3.0 / 4)


def test_admit_ms_p50(ctx):
    # admissions of 10 ms and 6 ms
    assert read("admit_ms_p50", ctx) == pytest.approx(8.0)


def test_batch_occupancy(ctx):
    # 50 tokens out, 10 of them first tokens of prefills: 40 over 10 steps x 8 slots
    assert read("batch_occupancy", ctx) == pytest.approx(50.0)


def test_decode_step_ms(ctx):
    # jit__lambda(111) ran 4 times (the 4 decode steps), 8 ms each; the
    # per-step argmax ran 3 times in the slice, 10 us each, and is not it
    assert read("decode_step_ms", ctx) == pytest.approx(8.0)
    # the slice's edge cut the last decode off (3 runs) but kept all four
    # argmaxes: the one that matches the step count exactly is not it
    mods = [m for m in ctx["view"]["modules"] if m[2] != 79000000] + [[0, "jit__argmax(444)", 88000000, 10000]]
    c = dict(ctx, view=dict(ctx["view"], modules=mods))
    assert read("decode_step_ms", c) == pytest.approx(8.0)


def _n(a):
    d = a["d_model"]
    d_in = 2 * d
    heads = d_in // a["ssm_head_dim"]
    mamba = d * (2 * d_in + 2 * a["ssm_state"] + heads) + d_in * d
    hd = d // a["n_heads"]
    shared = d * hd * 4 * a["n_heads"] + 3 * d * a["d_ff"]
    return a["n_layers"] * mamba + 1 * shared  # 2 layers, the shared block at layer 0 only


def test_prefill_mfu(ctx):
    a = ctx["arch"]
    # the admissions in the slice are log entries 5-3 = 2 and 3: prompts of 128 and 64
    flops = 2 * _n(a) * (128 + 64) + 2 * 2 * a["d_model"] * a["vocab_size"]
    want = 100 * flops / (8e-3 + 4e-3) / PEAK_FLOPS
    assert read("prefill_mfu", ctx) == pytest.approx(want)


def _ssd(s, a):
    h, q, p, n = 2 * a["d_model"] // a["ssm_head_dim"], a["ssm_chunk"], a["ssm_head_dim"], a["ssm_state"]
    nc = s // q
    pairs = q * (q + 1) / 2
    flops = h * nc * (2 * pairs * n + 2 * pairs * p + 2 * q * p * n)
    nbytes = h * s * 4 + h * s * p * 2 + 2 * s * n * 2 + h * s * p * 2 + h * nc * p * n * 4
    return flops, nbytes


def test_ssd_roofline(ctx):
    a = ctx["arch"]
    f1, b1 = _ssd(128, a)
    f2, b2 = _ssd(64, a)
    flops, nbytes = a["n_layers"] * (f1 + f2), a["n_layers"] * (b1 + b2)
    want = 100 * max(flops / PEAK_FLOPS, nbytes / PEAK_BW) / 3e-3
    assert read("ssd_roofline", ctx) == pytest.approx(want)


def test_busy_idle_and_breakdown(ctx):
    view = ctx["view"]
    # ops cover 12-14, 15-19, 23-31, 34-42, 52-55, 63-71, 79-87 ms
    assert tr.busy_s(view) == pytest.approx((2 + 4 + 8 + 8 + 3 + 8 + 8) / 1e3)
    assert tr.window_s(view) == pytest.approx(0.1)
    b = tr.breakdown(view)
    # fusion.2 is nested in the first decode's while loop: the loop's self time is 0
    ops = dict((k, v) for k, v in b["device_ops"])
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(0.032)]
    assert ops["while"] == pytest.approx(0.0)
    gaps = dict((k, v) for k, v in b["idle_gaps"])
    # 0-12: midpoint 6 ms, outside any span; 19-23 (mid 21): inside the comm span 21-22
    assert gaps["host.outside_spans"] > 0 and "bench.comm" in gaps


def test_readers_return_nothing_without_a_trace(ctx):
    c = copy.deepcopy(ctx)
    c["view"] = None
    for name in ("comm_ms_per_step", "admit_ms_p50", "decode_step_ms", "prefill_mfu", "ssd_roofline"):
        assert read(name, c) is None
