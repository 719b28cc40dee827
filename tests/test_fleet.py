"""The serving fleet (ISSUE 7): router tier + sharded-KV workers over the
comm layer.

Headline acceptance: **token-stream equivalence** — for the same request
trace, the 1-router × N-worker fleet over every backend (inline /
collective / shmem) emits exactly the per-request token sequences of the
single-host reference, including under admission backpressure (EAGAIN
observed, zero requests dropped).  Plus: the row-independence fact the
sharding stands on, free-slot-load routing, chunk stickiness, chunked
prefill never dispatching a single-shot prefill, and the lifecycle leak
regression (threads + live shmem segments flat across create/close
cycles).
"""
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SMOKES
from repro.core.comm.membership import GONE
from repro.core.comm.resources import ResourceLimits
from repro.core.comm.shmem import live_segments
from repro.models import decode_step, init_cache, init_params
from repro.serve import Fleet, FleetConfig, InferenceServer, ServeConfig

TRACE = [
    ([1, 2, 3], 4),
    ([4, 5], 5),
    ([6, 7, 8, 9, 10, 11, 12, 13, 14], 6),
    ([2, 2], 4),
    ([9, 1, 4], 5),
    ([7, 7, 7, 7, 7, 7], 6),
]


@pytest.fixture(scope="module")
def model():
    arch = SMOKES["tinyllama-1.1b"].variant(dtype="float32")
    return arch, init_params(jax.random.PRNGKey(0), arch)


def _run_single(model, chunk=0, slots=4):
    arch, params = model
    server = InferenceServer(
        arch, params,
        ServeConfig(slots=slots, context=64, transport="inline", prefill_chunk=chunk),
    )
    reqs = [server.submit(p, max_new=m) for p, m in TRACE]
    server.run_until_idle()
    assert all(r.done_event.is_set() for r in reqs)
    return [r.out_tokens for r in reqs]


def _run_fleet(model, transport, workers=2, chunk=0, slots=4, **cfg_kw):
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=workers, slots=slots, context=64, transport=transport,
                    prefill_chunk=chunk, **cfg_kw),
    )
    try:
        reqs = [fleet.submit(p, max_new=m) for p, m in TRACE]
        fleet.run_until_idle()
        assert all(r.done_event.is_set() for r in reqs), "fleet dropped a request"
        return [r.out_tokens for r in reqs], fleet
    finally:
        fleet.close()


def test_decode_rows_independent_of_batch_size(model):
    """The fact the slot sharding stands on: per-row decode results are
    bit-identical whatever the batch (= slot-shard) size, so splitting
    `slots` across workers cannot perturb any sequence."""
    arch, params = model
    c4 = init_cache(arch, 4, 64)
    c2 = init_cache(arch, 2, 64)
    t4, p4 = jnp.asarray([[3], [5], [7], [9]]), jnp.asarray([0, 0, 0, 0])
    t2, p2 = jnp.asarray([[3], [5]]), jnp.asarray([0, 0])
    for _ in range(4):
        l4, c4 = decode_step(params, arch, t4, p4, c4)
        l2, c2 = decode_step(params, arch, t2, p2, c2)
        assert jnp.array_equal(l4[:2, 0], l2[:, 0])  # bit-exact, not approx
        t4 = jnp.argmax(l4[:, 0], axis=-1)[:, None]
        t2 = jnp.argmax(l2[:, 0], axis=-1)[:, None]
        p4, p2 = p4 + 1, p2 + 1


@pytest.mark.parametrize("transport", ["inline", "collective", "shmem"])
def test_fleet_token_stream_equivalence(model, transport):
    """THE acceptance gate: same trace, same tokens, every backend."""
    ref = _run_single(model)
    out, fleet = _run_fleet(model, transport)
    assert out == ref
    # both workers actually served (the trace saturates both shards)
    assert all(w.core.tokens_out > 0 for w in fleet.workers)


@pytest.mark.parametrize("transport", ["inline", "collective", "shmem"])
def test_fleet_chunked_prefill_equivalence(model, transport):
    """Chunked prefill (prompts cross the wire in 4-token pieces,
    consumed interleaved with decode) preserves the token streams of the
    single-host reference with the SAME chunking — and no worker ever
    dispatches a single-shot prefill."""
    ref = _run_single(model, chunk=4)
    out, fleet = _run_fleet(model, transport, chunk=4)
    assert out == ref
    assert all(w.core.prefill_calls == 0 for w in fleet.workers)


def test_fleet_backpressure_eagain_requeues_never_drops(model):
    """An admission storm (tiny per-worker admission queue + bounded
    channel) must surface typed EAGAIN refusals AND still complete every
    request with reference-identical streams — re-queue, never drop."""
    ref = _run_single(model)
    limits = ResourceLimits(send_queue_depth=1, bounce_buffers=1, bounce_buffer_size=4_096)
    out, fleet = _run_fleet(
        model, "collective", admission_depth=1, limits=limits
    )
    assert out == ref
    assert fleet.eagain_events > 0  # backpressure genuinely triggered
    assert fleet.requeues == fleet.eagain_events
    assert fleet.completed == len(TRACE)  # zero dropped
    assert sum(w.eagain_refusals for w in fleet.workers) == fleet.eagain_events


def test_fleet_backpressure_on_put_backend(model):
    """The same storm over the put-capable shmem backend: refusals ride
    the one-sided response path, streams stay reference-identical."""
    ref = _run_single(model)
    out, fleet = _run_fleet(model, "shmem", admission_depth=1)
    assert out == ref
    assert fleet.eagain_events > 0
    assert fleet.completed == len(TRACE)


def test_fleet_routes_by_free_slot_load(model):
    """With both workers empty, admissions alternate by headroom: 4
    concurrent requests over 2 workers land 2 and 2 (deterministic ties
    to the lowest id)."""
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport="inline",
                    admission_depth=4),
    )
    try:
        reqs = [fleet.submit(p, max_new=m) for p, m in TRACE[:4]]
        fleet.step()
        seen = [len(w.rids_seen) for w in fleet.workers]
        assert seen == [2, 2], seen
        fleet.run_until_idle()
        assert all(r.done_event.is_set() for r in reqs)
    finally:
        fleet.close()


def test_fleet_chunk_stickiness(model):
    """Every follow-up chunk of a request goes to the worker that
    admitted its first chunk (cache affinity: the prefix KV lives
    there)."""
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=3, slots=3, context=64, transport="inline",
                    prefill_chunk=2),
    )
    try:
        long_prompts = [[i + 1] * 9 for i in range(6)]  # 9 tokens = 5 chunks
        reqs = [fleet.submit(p, max_new=3) for p in long_prompts]
        fleet.run_until_idle()
        assert all(r.done_event.is_set() for r in reqs)
        # each rid was admitted by exactly one worker, and that worker's
        # core consumed the FULL prompt for it (all chunks arrived there:
        # position after prefill+decode = len(prompt) + max_new - 1)
        admitted = {rid: w.wid for w in fleet.workers for rid in w.rids_seen}
        assert len(admitted) == len(reqs)
        counts = [len(w.rids_seen) for w in fleet.workers]
        assert counts == [2, 2, 2], counts  # load-balanced too
        assert [len(r.out_tokens) for r in reqs] == [3] * 6
    finally:
        fleet.close()


def test_fleet_lifecycle_no_thread_or_segment_leak(model):
    """50 create/close cycles of a 4-worker shmem fleet leave the process
    thread count and the live shmem-segment census flat (the PR 5
    lci_prg{n} join fix, extended to worker channels)."""
    arch, params = model
    cfg = dict(workers=4, slots=4, context=64, transport="shmem")
    # warm one full serve cycle so jit caches don't count as "growth"
    fleet = Fleet(arch, params, FleetConfig(**cfg))
    r = fleet.submit([1, 2, 3], max_new=2)
    fleet.run_until_idle()
    assert r.done_event.is_set()
    fleet.close()
    threads0, segs0 = threading.active_count(), live_segments()
    for i in range(50):
        fleet = Fleet(arch, params, FleetConfig(**cfg))
        if i % 10 == 0:  # periodically exercise the channels, not just ctor
            req = fleet.submit([1, 2, 3], max_new=2)
            fleet.run_until_idle()
            assert req.done_event.is_set()
        fleet.close()
    assert threading.active_count() == threads0
    assert live_segments() == segs0


@pytest.mark.parametrize("transport,expect_puts", [("shmem", True), ("collective", False)])
def test_fleet_put_selection_follows_capabilities(model, transport, expect_puts):
    """Response delivery rides ``post_put_signal`` into router-owned
    landing slots exactly when the backend advertises
    ``one_sided_put`` — never on capability-less backends, always on the
    shmem fleet (selection is purely capability-driven, per channel)."""
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport=transport),
    )
    try:
        for ch in fleet.channels:
            assert ch._put_responses == ch.server.capabilities.one_sided_put
            assert ch._put_responses == expect_puts
        reqs = [fleet.submit(p, max_new=m) for p, m in TRACE[:3]]
        fleet.run_until_idle()
        assert all(r.done_event.is_set() for r in reqs)
        puts = fleet.group.stats.puts
        assert (puts > 0) == expect_puts, f"puts={puts} on {transport}"
    finally:
        fleet.close()


def test_admission_cost_flat_in_slot_count(model):
    """Satellite 4: admitting one request must not pay for every other
    slot.  The old path rebuilt the full KV pytree per admission
    (``jax.tree.map`` splice => cost ~ O(slots)); the
    ``dynamic_update_slice`` fix makes it ~ O(1) in slot count.  Pin it:
    admission at 32 slots stays well under the ~16x the per-leaf rebuild
    would cost vs 2 slots (generous 6x bound for CI noise)."""
    import time

    from repro.serve.server import DecodeCore

    arch, params = model

    def admit_time(slots):
        core = DecodeCore(arch, params, slots=slots, context=64)
        sink = lambda *a: None

        class _R:  # duck-typed request: just what admit() reads
            def __init__(self, rid):
                # max_new=1 finishes at the prefill step, freeing the slot,
                # so repeated admissions time the admission path alone
                self.rid, self.prompt, self.max_new = rid, [1, 2, 3], 1
                self.submitted_at = 0.0  # no client stamp: queue wait reads 0

        core.admit(_R(0), sink)  # warm the jit caches for this shape
        best = float("inf")
        for rep in range(5):
            t0 = time.perf_counter()
            core.admit(_R(rep + 1), sink)
            best = min(best, time.perf_counter() - t0)
        return best

    t_small, t_big = admit_time(2), admit_time(32)
    assert t_big < 6 * t_small, (
        f"admission scaled with slot count: {t_big*1e3:.2f}ms @32 vs "
        f"{t_small*1e3:.2f}ms @2"
    )


def test_fleet_single_worker_degenerates_to_single_host(model):
    """workers=1 is the single-host server modulo the router hop."""
    ref = _run_single(model)
    out, _ = _run_fleet(model, "collective", workers=1)
    assert out == ref


# ------------------------------------------------ elastic fleet (ISSUE 8)
@pytest.mark.parametrize("transport", ["inline", "collective", "shmem"])
def test_fleet_mid_decode_leave_bit_identical(model, transport):
    """THE elastic acceptance gate: a worker leaves MID-DECODE, its KV
    slots hand off to a successor as checkpoint.snapshot payloads over the
    existing channel, and every request's token stream stays bit-identical
    to the single-host reference — zero drops, on every backend."""
    ref = _run_single(model)
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport=transport, max_workers=3),
    )
    try:
        reqs = [fleet.submit(p, max_new=m) for p, m in TRACE]
        for _ in range(3):
            fleet.step()  # decode genuinely underway on worker 0
        fleet.add_worker()  # the successor joins on the spare rank...
        assert fleet.leave_worker(0) is True  # ...and worker 0 drains out
        fleet.run_until_idle()
        assert all(r.done_event.is_set() for r in reqs), "leave dropped a request"
        assert [r.out_tokens for r in reqs] == ref  # bit-identical continuation
        assert fleet.completed == len(TRACE)
        assert fleet.handoffs >= 1  # slots really moved mid-stream
        assert (fleet.joins, fleet.leaves) == (1, 1)
        assert fleet.membership.state(0) == GONE
        assert sum(w.adoptions for w in fleet.workers if w is not None) == fleet.handoffs
    finally:
        fleet.close()


@pytest.mark.parametrize("transport", ["inline", "collective"])
def test_fleet_mid_prefill_leave_chunked(model, transport):
    """A leave while chunked prefill is still streaming: the snapshot
    carries the open prefill queue, sticky chunk routing re-points to the
    adopter, and a chunk that outran the splice is stashed — streams stay
    reference-identical."""
    ref = _run_single(model, chunk=4)
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport=transport,
                    prefill_chunk=4, max_workers=3),
    )
    try:
        reqs = [fleet.submit(p, max_new=m) for p, m in TRACE]
        fleet.step()  # prompts admitted, chunk plans still draining
        fleet.add_worker()
        fleet.leave_worker(0)
        fleet.run_until_idle()
        assert all(r.done_event.is_set() for r in reqs)
        assert [r.out_tokens for r in reqs] == ref
        assert fleet.completed == len(TRACE)
    finally:
        fleet.close()


def test_fleet_join_leave_cycles_threads_segments_flat(model):
    """25 join/leave cycles against a live shmem fleet: the spare rank's
    pre-provisioned channel/slab is REUSED every cycle, so the process
    thread count and the live shmem-segment census never move."""
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport="shmem", max_workers=3),
    )
    try:
        wid = fleet.add_worker()  # warm one full cycle (jit, channels)
        fleet.leave_worker(wid)
        r = fleet.submit([1, 2, 3], max_new=2)
        fleet.run_until_idle()
        assert r.done_event.is_set()
        threads0, segs0 = threading.active_count(), live_segments()
        ranks = set()
        for i in range(25):
            ranks.add(fleet.add_worker())
            if i % 5 == 0:  # serve through some cycles, not just churn
                req = fleet.submit([2, 3, 4], max_new=2)
            fleet.leave_worker(2)
            fleet.run_until_idle()
            assert threading.active_count() == threads0
            assert live_segments() == segs0
        assert ranks == {2}  # the same rank slot every cycle — true reuse
        assert fleet.joins == 26 and fleet.leaves == 26
        assert fleet.completed == 6  # warm + 5 churn-cycle requests, zero lost
    finally:
        fleet.close()


def test_fleet_abandoned_worker_swept_and_rank_reused(model):
    """Satellite regression: a fleet worker that dies WITHOUT leave() is
    reaped by the membership finalizer sweep — its rank returns to the
    pool and the fleet keeps serving."""
    import gc

    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport="inline", max_workers=3),
    )
    try:
        w = fleet.workers[1]
        fleet.workers[1] = None  # the router's strong ref goes away...
        del w  # ...and the worker dies with no leave()
        gc.collect()
        assert fleet.membership.sweep() == [1]
        assert fleet.membership.state(1) == GONE
        assert fleet.membership.active_ranks() == (0,)
        assert fleet.add_worker() == 1  # the abandoned rank is reusable
        r = fleet.submit([1, 2, 3], max_new=2)
        fleet.run_until_idle()
        assert r.done_event.is_set()
    finally:
        fleet.close()


def test_fleet_leave_edge_cases(model):
    """Double leave is idempotent; the last active worker may not leave;
    a full fleet refuses further joins."""
    arch, params = model
    fleet = Fleet(
        arch, params,
        FleetConfig(workers=2, slots=4, context=64, transport="inline", max_workers=2),
    )
    try:
        assert fleet.leave_worker(1) is True
        assert fleet.leave_worker(1) is False  # idempotent no-op
        with pytest.raises(ValueError, match="last active"):
            fleet.leave_worker(0)
        assert fleet.add_worker() == 1  # GONE rank rejoins...
        with pytest.raises(ValueError, match="max_workers"):
            fleet.add_worker()  # ...but the fleet is bounded
    finally:
        fleet.close()
