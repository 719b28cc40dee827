"""The serving loop's own spans and counters: ``serve.*`` annotations read
back from a CPU profiler session through ``bench.serve_trace``,
``DecodeCore.host_syncs`` and ``DecodeCore.compiles``, and the submit stamp
that rides the request message."""
import os
import sys

import jax
import pytest

from repro.configs import SMOKES
from repro.models import init_params
from repro.serve import InferenceServer, ServeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import serve_trace as st  # noqa: E402
from bench import trace as tr  # noqa: E402

SPANS = {
    "serve.comm", "serve.admit", "serve.admit.scratch", "serve.admit.prefill",
    "serve.admit.splice", "serve.admit.first_token", "serve.decode", "serve.decode.dispatch",
    "serve.decode.sync", "serve.decode.emit", "serve.flush", "serve.deliver",
}
PROMPTS = [[1, 2, 3], [4, 5, 6], [7, 8, 9, 10]]


@pytest.fixture(scope="module")
def model():
    arch = SMOKES["tinyllama-1.1b"].variant(dtype="float32")
    return arch, init_params(jax.random.PRNGKey(0), arch)


def _server(model, transport="collective"):
    arch, params = model
    return InferenceServer(arch, params, ServeConfig(slots=2, context=64, transport=transport))


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """Three requests served under a profiler session: (server, requests, view)."""
    server = _server(model)
    server.submit([5, 5, 5], max_new=2)  # compile outside the session
    server.run_until_idle()
    d = str(tmp_path_factory.mktemp("serve-trace"))
    jax.profiler.start_trace(d)
    try:
        reqs = [server.submit(p, max_new=3) for p in PROMPTS]
        server.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    return server, reqs, st.view_from_xplane(tr.find_xplane(d))


def _named(view, name):
    return [s for s in view["spans"] if s[0] == name]


def _inside(child, parents):
    return any(p[1] <= child[1] and child[1] + child[2] <= p[1] + p[2] for p in parents)


def test_every_span_appears(traced):
    _, _, view = traced
    assert SPANS <= {s[0] for s in view["spans"]}


def test_children_nest_in_their_parents(traced):
    _, _, view = traced
    for parent in ("serve.admit", "serve.decode"):
        outer = _named(view, parent)
        kids = [s for s in view["spans"] if s[0].startswith(parent + ".")]
        assert kids and all(_inside(k, outer) for k in kids), parent
    assert all(_inside(d, _named(view, "serve.comm")) for d in _named(view, "serve.deliver"))


def test_admission_spans_carry_rid_and_queue_wait(traced):
    _, reqs, view = traced
    admits = _named(view, "serve.admit")
    assert sorted(a[3]["rid"] for a in admits) == sorted(r.rid for r in reqs)
    for a in admits:
        assert a[3]["queued_ns"] >= 0
        assert a[3]["prompt"] == len(PROMPTS[[r.rid for r in reqs].index(a[3]["rid"])])
    # per-step spans carry counts: 3 requests of 3 tokens, the first at admission
    assert sum(s[3]["tokens"] for s in _named(view, "serve.decode.emit")) == 3 * 2
    assert sum(s[3]["tokens"] for s in _named(view, "serve.flush")) == 3 * 3
    assert sum(s[3]["tokens"] for s in _named(view, "serve.deliver")) == 3 * 3
    assert sum(s[3]["requests"] for s in _named(view, "serve.comm")) == 3


def test_host_syncs_are_steps_plus_admissions(traced):
    server, _, _ = traced
    core = server.core
    assert core.prefill_calls == 4
    assert core.host_syncs == core.steps + core.prefill_calls


def test_compiles_count_new_prompt_lengths_only(model):
    server = _server(model, transport="inline")
    core = server.core
    server.submit([1, 2, 3], max_new=3)
    server.run_until_idle()
    warm = core.compiles
    assert warm >= 3  # prefill, splice, decode
    server.submit([4, 5, 6], max_new=3)  # a length already compiled
    server.run_until_idle()
    assert core.compiles == warm
    server.submit([4, 5, 6, 7, 8], max_new=3)  # a new length: one more prefill
    server.run_until_idle()
    assert core.compiles == warm + 1


def test_submit_stamp_rides_the_request_message(model):
    inline = _server(model, transport="inline")
    collective = _server(model)
    streams = []
    for server in (inline, collective):
        reqs = [server.submit(p, max_new=4) for p in PROMPTS]
        if server is collective:
            server._comm_step()  # the requests cross the channel
            assert [r.submitted_at for r in server._pending] == [r.submitted_at for r in reqs]
        server.run_until_idle()
        streams.append([r.out_tokens for r in reqs])
    assert streams[0] == streams[1]


def test_launcher_prints_the_counters(monkeypatch, capsys):
    from repro.launch import serve as launch

    monkeypatch.setattr(launch, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "tinyllama-1.1b", "--requests", "2", "--clients", "1", "--slots", "2",
        "--context", "64", "--max-prefill", "8", "--max-new", "3", "--prompt-len", "4",
    ])
    assert launch.main() == 0
    out = capsys.readouterr().out
    # one read per admission and per decode step; prefill, splice and decode compiled
    steps = int(out.split("engine_steps=")[1].split()[0])
    assert f"host_syncs={steps + 2} " in out
    assert int(out.split("compiles=")[1].split()[0]) >= 3
