"""The check that decides ``correct`` catches the faults a served cell can
have.  Each test drives a whole run of a serving cell on the CPU at smoke
size (the harness's look for a chip skipped), with the timed path broken
underneath, and sees ``correct`` come out false; the same run unbroken
comes out true.  The limit is the cell's own."""
import pytest

from bench_smoke import smoke_ctx

from bench.faults import FAULTS

CELLS = ["mamba2-chat-burst", "mamba2-chat-steady"]


def _run(workload, seed, breaker=None):
    from bench.drivers import serve

    _, ctx = smoke_ctx(workload, seed)
    build = serve._build
    if breaker is not None:
        def broken(c):
            server = build(c)
            breaker(server)
            return server

        serve._build = broken
    try:
        return serve.run(ctx)
    finally:
        serve._build = build


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = _run(workload, 2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(workload, fault):
    res = _run(workload, 2**31 + 11, FAULTS[fault])
    assert not res["correct"], res["checks"]
