"""Reading a profiler trace: a small XSpace written as text, in the shape
a TPU v5e trace of a serving window has (``XLA Modules`` named with their
fingerprints, ``XLA Ops`` named by HLO text and nested, the benchmark's
``bench.*`` spans on a host line), reduced by ``bench.trace``."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.textproto")


@pytest.fixture(scope="module")
def view(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(DATA) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(raw)
    assert tr.find_xplane(str(path.parent)) == str(path)
    return tr.view_from_xplane(str(path))


def test_events_on_one_clock(view):
    ms = 1e6
    assert view["window"] == [1 * ms, 31 * ms]
    assert view["modules"] == [[0, "jit__lambda(111)", 2 * ms, 8 * ms], [0, "jit__lambda(222)", 21 * ms, 4 * ms]]
    assert [o[1] for o in view["ops"]] == ["while", "fusion", "ssd_chunk_kernel"]
    assert sorted(s[0] for s in view["spans"]) == ["bench.admit", "bench.decode", "bench.traced_window"]


def test_busy_self_time_and_gaps(view):
    assert tr.busy_s(view) == pytest.approx(10e-3)  # 2-10 ms and 22-24 ms
    st = tr.self_times(view, 0)
    assert st["while"] == pytest.approx(3e6) and st["fusion"] == pytest.approx(5e6)
    gaps = dict(tr.breakdown(view)["idle_gaps"])
    # 1-2 ms inside the decode span; 10-22 ms: midpoint 16 ms, no span open;
    # 24-31 ms: midpoint 27.5, no span open (the admission ended at 26)
    assert gaps["bench.decode"] == pytest.approx(1e-3)
    assert gaps["host.outside_spans"] == pytest.approx(19e-3)
