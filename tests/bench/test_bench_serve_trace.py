"""``bench.serve_trace`` on the recorded slice of ``data/serve_view.json``
with ``data/serve_spans.json``'s ``serve.*`` spans laid over it (nested in
its ``bench.*`` spans, on the same clock), worked out here by hand; and the
per-layer readers the benchmark has, unmoved by the program's spans."""
import copy
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import serve_trace as st  # noqa: E402
from bench import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("comm_ms_per_step", "admit_ms_p50", "batch_occupancy", "decode_step_ms", "prefill_mfu", "ssd_roofline")


@pytest.fixture()
def ctx():
    with open(os.path.join(DATA, "serve_view.json")) as f:
        c = json.load(f)
    c["admits"] = [tuple(a) for a in c["admits"]]
    return c


@pytest.fixture()
def sctx(ctx):
    with open(os.path.join(DATA, "serve_spans.json")) as f:
        extra = json.load(f)
    c = copy.deepcopy(ctx)
    c["view"]["spans"] += extra["spans"]
    return c


@pytest.mark.parametrize("name", READERS)
def test_program_spans_leave_the_readers_as_they_were(ctx, sctx, name):
    read = importlib.import_module(f"bench.metrics.{name}").read
    assert read(ctx) is not None
    assert read(sctx) == read(ctx)


def test_numbers(sctx):
    n = st.numbers(sctx["view"])
    # two admissions in the slice waited 30 and 10 ms; the one that crosses
    # the slice's end (500 ms) is left out: 10 + 0.95 * 20
    assert n["queue_wait_ms_p95"] == pytest.approx(29.0)
    # first-token reads of 7.8 and 4.4 ms
    assert n["admit_sync_ms_p50"] == pytest.approx(6.1)
    # 4 decode spans of 9.8 ms less their 8.5 ms syncs, plus flushes of
    # 0.8 + 0.5 + 0.5 + 0.5 ms, over 4 decode steps
    assert n["decode_host_ms_per_step"] == pytest.approx((4 * (9.8 - 8.5) + 2.3) / 4)
    # 2 first-token reads and 4 decode reads over 4 decode steps
    assert n["host_syncs_per_step"] == pytest.approx(1.5)


def test_numbers_without_the_program_spans(ctx):
    assert all(v is None for v in st.numbers(ctx["view"]).values())


def test_split(sctx):
    s = st.split(sctx["view"])
    assert s["serve.admit"]["n"] == 2  # the third crosses the slice's end
    assert s["serve.admit.first_token"]["total_ms"] == pytest.approx(7.8 + 4.4)
    # ops run at 12-14 and 15-19 ms inside the first read (12-19.8), and
    # at 52-55 ms inside the second (51.4-55.8)
    assert s["serve.admit.first_token"]["busy_ms"] == pytest.approx(2 + 4 + 3)
    assert s["serve.decode.sync"]["busy_ms"] == pytest.approx(4 * 8)


def test_idle_gaps_named_by_the_innermost_span_of_either_family(ctx, sctx):
    gaps = dict(tr.breakdown(sctx["view"], top=50)["idle_gaps"])
    # 14-15 ms: inside bench.admit, serve.admit and its first-token read
    assert gaps["serve.admit.first_token"] == pytest.approx(1e-3)
    assert "bench.admit" not in gaps and "serve.admit" not in gaps
    # 19-23 ms (mid 21): the comm step, which the response's delivery
    # (21.2-21.4 ms) does not cover
    assert gaps["serve.comm"] == pytest.approx(4e-3)
    # 31-34 ms (mid 32.5): the token batch's flush
    assert gaps["serve.flush"] == pytest.approx(3e-3)
    # the same slice without the program's spans names the benchmark's
    old = dict(tr.breakdown(ctx["view"], top=50)["idle_gaps"])
    assert old["bench.comm"] == pytest.approx(4e-3) and sum(old.values()) == pytest.approx(sum(gaps.values()))
