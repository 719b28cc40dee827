"""Continuous-batching inference server over the shared comm layer.

vLLM-style slot scheduler on the JAX decode path: a fixed pool of ``slots``
shares one ring KV cache; requests arrive asynchronously (any thread may
submit — the paper's multithreaded-communication model applied to
serving), prefill fills a free slot, and every engine step decodes ALL
active slots in one batched ``decode_step``.  Finished sequences free
their slot immediately; new requests join between steps (continuous
batching, no head-of-line blocking).

**The request/response hand-off is the repo's communication abstraction**
(ISSUE 5): with ``transport='collective'`` (the default), requests and
per-token responses travel as bytes through :class:`~repro.core.comm.
interface.CommInterface` verbs on a :class:`~repro.core.comm.collective.
CommChannel` — typed EAGAIN backpressure parks and retries under the
shared :class:`~repro.core.comm.resources.ResourceLimits`, token
completions for all active slots aggregate into ONE response message per
engine step (§2.2.2 applied to serving), and the engine loop drives the
SAME :class:`~repro.core.comm.progress.ProgressEngine` as the parcelports
(policy via ``ProgressPolicy.for_config``, exactly like ``LCIPPConfig`` /
``SimConfig``).  ``transport='inline'`` keeps the legacy direct hand-off
as the round-trip parity reference — both paths produce identical
responses for the same request stream (tests/test_executor_serve.py).

Since ISSUE 7 the slot scheduler + batched decode live in
:class:`DecodeCore`, shared verbatim between this single-host server and
the fleet's :class:`~repro.serve.fleet.ModelWorker` — the fleet shards
the slot space across workers but runs the SAME math, which is what makes
the token-stream equivalence tests exact rather than approximate.

**Spans and counters.** Each stage of the loop opens a
``jax.profiler.TraceAnnotation`` under the ``serve.`` prefix (``serve.comm``,
``serve.admit`` with its ``.scratch`` / ``.prefill`` / ``.splice`` /
``.first_token`` children, ``serve.decode`` with ``.dispatch`` / ``.sync`` /
``.emit``, ``serve.flush`` and ``serve.deliver``).  They are inert unless a
profiler session is open, and then land on the device trace's clock.
``serve.admit`` carries the request's ``rid`` and ``queued_ns``, admission
start less the client's ``submitted_at``.  ``DecodeCore.host_syncs`` counts
the device-to-host reads that block the loop and ``DecodeCore.compiles`` the
growth of its jit caches.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ArchConfig
from ..core.comm.collective import CommChannel
from ..core.comm.progress import ProgressEngine, ProgressPolicy, run_step
from ..core.comm.resources import ResourceLimits
from ..core.comm.wire import decode_msg, encode_msg
from ..models import decode_step, init_cache, prefill

__all__ = ["ServeConfig", "Request", "DecodeCore", "InferenceServer"]


@dataclass
class ServeConfig:
    slots: int = 4  # concurrent sequences (decode batch)
    context: int = 256  # KV slots per sequence
    max_prefill: int = 64  # prompt length bucket (padded)
    greedy: bool = True
    # Request/response hand-off: 'collective' rides CommInterface verbs on
    # a CollectiveComm pair driven by the shared ProgressEngine; 'shmem'
    # swaps in the true one-sided shared-memory transport (responses ride
    # put into the router-owned response queue whenever the backend's
    # Capabilities advertise one_sided_put — ISSUE 6); 'inline' is the
    # legacy direct hand-off (the parity reference in tests).
    transport: str = "collective"
    # Chunked prefill (ISSUE 7): 0 = classic single-shot prefill at
    # admission; N > 0 = prompts are consumed incrementally, interleaved
    # with decode of the other slots, and cross the fleet transport split
    # into N-token chunk messages — prefill never stalls decode.
    prefill_chunk: int = 0
    # ProgressPolicy.for_config axes — the same fields, by design, as
    # LCIPPConfig and the DES SimConfig: the serving hot path sweeps the
    # §5.3 policy ladder like any parcelport variant.
    progress_mode: str = "explicit"  # 'explicit' | 'implicit'
    lock_mode: str = "none"
    progress_workers: int = 0
    # The shared resource model (§3.3.4) bounding the hand-off channel.
    limits: ResourceLimits = field(default_factory=ResourceLimits)


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out_tokens: List[int] = field(default_factory=list)
    done_event: threading.Event = field(default_factory=threading.Event)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None


# emit(req, token, done) — one generated token leaves the model side.
EmitFn = Callable[[Request, int, bool], None]


class DecodeCore:
    """Slot scheduler + batched decode, independent of any transport.

    Owns the batched ring KV cache (``init_cache(arch, slots, context)``),
    per-slot positions / budgets, and the two jitted entry points.  The
    single-host :class:`InferenceServer` runs ONE core with ``cfg.slots``
    slots; the fleet runs N cores of ``slots // n_workers`` each.  Rows of
    the batched decode are computed independently (verified bit-exact in
    tests/test_fleet.py), so sharding the slot space across cores cannot
    change any request's token stream.

    Two admission modes:

    * **single-shot** (``prefill_chunk == 0``): the whole prompt runs
      through the jitted ``prefill`` on a scratch cache and is spliced
      into the slot — one dispatch, first token emitted at admission.
    * **chunked** (``prefill_chunk > 0``): the slot starts empty and
      consumes ONE prompt token per engine step through the same batched
      ``decode_step`` that serves the decoding slots (teacher forcing).
      Per-step work is one uniform batched decode regardless of prompt
      length — a long prompt can never stall other slots' decode.  Chunk
      arrivals may lag the consumer; a starved slot simply re-feeds its
      last token WITHOUT advancing its position, and the garbage KV row
      is overwritten when the real token arrives (the cache write is
      position-addressed), so stall timing cannot perturb the stream.
    """

    def __init__(
        self,
        arch: ArchConfig,
        params: Any,
        slots: int,
        context: int,
        max_prefill: int = 64,
        prefill_chunk: int = 0,
    ):
        self.arch, self.params = arch, params
        self.slots, self.context = slots, context
        self.max_prefill, self.prefill_chunk = max_prefill, prefill_chunk
        self._slots: List[Optional[Request]] = [None] * slots
        self._positions = np.zeros((slots,), np.int32)
        self._remaining = np.zeros((slots,), np.int32)
        self._last_tok = np.zeros((slots,), np.int32)
        # one shared batched cache; per-slot prefill via single-slot caches
        self.cache = init_cache(arch, slots, context)
        # zeroed single-slot row: splicing it in resets a recycled slot
        # (stale position tags must not leak into a new sequence)
        self._fresh_row = init_cache(arch, 1, context)
        self._prefill_one = jax.jit(
            lambda p, b, c: prefill(p, arch, b, c), donate_argnums=(2,)
        )
        self._decode = jax.jit(
            lambda p, t, pos, c: decode_step(p, arch, t, pos, c), donate_argnums=(3,)
        )

        # ONE jitted, donated cache splice (ISSUE 7 satellite): the old
        # per-admission `jax.tree.map(splice, ...)` ran a separate
        # dynamic_update_slice dispatch per cache leaf OUTSIDE jit,
        # copying the full cache each time — admission cost grew with the
        # total slot count.  Donating the full cache lets XLA update the
        # one row in place: admission cost is now flat in `slots`
        # (pinned by test_admission_cost_flat_in_slot_count).
        def _splice(full, piece, slot):
            def leaf(f, pc):
                if f.ndim >= 2 and pc.shape[0] == f.shape[0]:
                    # stacked leading layer dim, batch at axis 1
                    return jax.lax.dynamic_update_slice_in_dim(f, pc, slot, axis=1)
                return f

            return jax.tree.map(leaf, full, piece)

        self._splice = jax.jit(_splice, donate_argnums=(0,))
        # the jitted programs as built, whose caches `compiles` watches
        self._jits = (self._prefill_one, self._decode, self._splice)
        self._jit_entries = 0  # compiled programs in their caches, last look
        self.steps = 0
        self.tokens_out = 0
        self.prefill_calls = 0  # single-shot prefill dispatches (0 when chunked)
        self.host_syncs = 0  # device-to-host reads that block the loop
        self.compiles = 0  # entries the jit caches above grew by
        # worst prompt-tokens-of-prefill-work attributed to a single engine
        # step — the burst chunked prefill exists to bound (≤ active slots
        # per step vs a whole prompt per admission single-shot)
        self.max_prefill_burst = 0
        self._pending_burst = 0  # single-shot prefill work since last step
        # chunked-prefill state: slot -> queued prompt tokens / open flag
        self._prefill_queue: Dict[int, deque] = {}
        self._prefill_open: Dict[int, bool] = {}
        self._rid_slot: Dict[int, int] = {}

    # ------------------------------------------------------------- occupancy
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    def active(self) -> bool:
        return any(r is not None for r in self._slots)

    # ------------------------------------------------------------- admission
    def admit(self, req: Request, emit: EmitFn, more_chunks: bool = False) -> int:
        """Place ``req`` into the lowest free slot.  With chunked prefill,
        ``req.prompt`` may hold only the FIRST chunk; ``more_chunks=True``
        keeps the slot in the prefilling state until :meth:`feed_chunk`
        delivers the rest.  Returns the slot index."""
        slot = self.free_slots()[0]
        chunked = self.prefill_chunk > 0
        prompt = req.prompt if chunked and more_chunks else req.prompt[: self.max_prefill]
        # a request with no client stamp (the fleet's own format) reads 0
        queued_ns = int((time.monotonic() - req.submitted_at) * 1e9) if req.submitted_at else 0
        with TraceAnnotation("serve.admit", rid=req.rid, prompt=len(prompt), queued_ns=queued_ns):
            if chunked:
                # reset the recycled row (zero KV, position tags = -1), then
                # consume the prompt one token per step through decode_step
                self.cache = self._splice(self.cache, self._fresh_row, slot)
                self._slots[slot] = req
                self._positions[slot] = 0
                self._remaining[slot] = req.max_new
                self._prefill_queue[slot] = deque(prompt)
                self._prefill_open[slot] = more_chunks
                self._rid_slot[req.rid] = slot
            else:
                # single-sequence prefill on a scratch cache, then splice into slot
                with TraceAnnotation("serve.admit.scratch"):
                    one = init_cache(self.arch, 1, self.context)
                with TraceAnnotation("serve.admit.prefill"):
                    toks = np.zeros((1, self.max_prefill), np.int32)
                    toks[0, -len(prompt) :] = prompt  # left-pad; ring positions still 0..n
                    batch = {"tokens": jnp.asarray(toks[:, -len(prompt) :])}
                    logits, one = self._prefill_one(self.params, batch, one)
                self.prefill_calls += 1
                self._pending_burst += len(prompt)
                with TraceAnnotation("serve.admit.splice"):
                    self.cache = self._splice(self.cache, one, slot)
                with TraceAnnotation("serve.admit.first_token"):
                    tok = int(jnp.argmax(logits[0, -1]))
                self.host_syncs += 1
                done = req.max_new <= 1
                self._slots[slot] = None if done else req
                self._positions[slot] = len(prompt)
                self._remaining[slot] = req.max_new - 1
                self._last_tok[slot] = tok
                self._rid_slot[req.rid] = slot
                if done:
                    self._rid_slot.pop(req.rid, None)
                self.tokens_out += 1
                emit(req, tok, done)
        self._note_compiles()
        return slot

    def _note_compiles(self) -> None:
        # entries of the jit caches (JAX's private `_cache_size()`): a
        # program loaded from the persistent compile cache counts too
        prefill_fn, decode_fn, splice_fn = self._jits
        entries = prefill_fn._cache_size() + decode_fn._cache_size() + splice_fn._cache_size()
        self.compiles += entries - self._jit_entries
        self._jit_entries = entries

    def feed_chunk(self, rid: int, tokens: List[int], last: bool) -> None:
        """Append a follow-up prompt chunk for an admitted request."""
        slot = self._rid_slot[rid]
        assert self._prefill_open.get(slot), f"slot {slot} is not expecting chunks"
        self._prefill_queue[slot].extend(tokens)
        if last:
            self._prefill_open[slot] = False

    def prefilling(self, rid: int) -> bool:
        slot = self._rid_slot.get(rid)
        return slot is not None and slot in self._prefill_queue

    # ----------------------------------------------------------------- step
    def step(self, emit: EmitFn) -> bool:
        """One batched decode over all active slots.  Decoding slots
        advance one generated token; prefilling slots consume one prompt
        token (emitting their first token when the prompt is exhausted);
        starved prefilling slots hold position.  Returns False when no
        slot is active (no decode dispatched)."""
        active = [i for i, r in enumerate(self._slots) if r is not None]
        if not active:
            return False
        with TraceAnnotation("serve.decode", active=len(active)):
            fed: Dict[int, int] = {}  # slot -> prompt token fed this step
            for i in active:
                q = self._prefill_queue.get(i)
                if q is None:
                    continue  # plain decoding slot
                if q:
                    fed[i] = self._last_tok_feed(i, q.popleft())
                # else: starved mid-prefill — re-feed last token, hold position
            with TraceAnnotation("serve.decode.dispatch"):
                toks = jnp.asarray(self._last_tok[:, None])
                pos = jnp.asarray(self._positions)
                logits, self.cache = self._decode(self.params, toks, pos, self.cache)
            with TraceAnnotation("serve.decode.sync"):
                nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1), np.int32)
            self.host_syncs += 1
            before = self.tokens_out
            with TraceAnnotation("serve.decode.emit") as span:
                for i in active:
                    req = self._slots[i]
                    if i in self._prefill_queue:
                        if i not in fed:
                            continue  # starved: nothing advanced
                        self._positions[i] += 1
                        if self._prefill_queue[i] or self._prefill_open[i]:
                            continue  # more prompt to consume: no emission yet
                        # the LAST prompt token was just fed: its logits give the
                        # first generated token — the chunked analogue of the
                        # single-shot prefill's argmax(logits[0, -1])
                        del self._prefill_queue[i]
                        del self._prefill_open[i]
                    else:
                        self._positions[i] += 1
                    self._remaining[i] -= 1
                    self._last_tok[i] = nxt[i]
                    done = self._remaining[i] <= 0
                    self.tokens_out += 1
                    emit(req, int(nxt[i]), done)
                    if done:
                        self._slots[i] = None
                        self._rid_slot.pop(req.rid, None)
                span.set_metadata(tokens=self.tokens_out - before)
        self._note_compiles()
        self.steps += 1
        burst = self._pending_burst + len(fed)
        if burst > self.max_prefill_burst:
            self.max_prefill_burst = burst
        self._pending_burst = 0
        return True

    def _last_tok_feed(self, slot: int, tok: int) -> int:
        self._last_tok[slot] = tok
        return tok

    # ------------------------------------------------ slot handoff (ISSUE 8)
    def extract_slot(self, slot: int) -> tuple:
        """Snapshot one ACTIVE slot for handoff to another core and free
        it.  Returns ``(state, meta)``: ``state`` is the slot's KV row in
        the same single-slot structure as ``_fresh_row`` (the inverse of
        ``_splice``'s leaf rule), ``meta`` the scalar scheduler state.
        The cache write is position-addressed and batched-decode rows are
        independent, so splicing these exact bits into ANY core's free
        slot continues the token stream bit-identically."""
        req = self._slots[slot]
        assert req is not None, f"slot {slot} is empty"

        def leaf(f, pc):
            if f.ndim >= 2 and pc.shape[0] == f.shape[0]:
                return jax.lax.dynamic_slice_in_dim(f, slot, 1, axis=1)
            return pc

        state = jax.tree.map(leaf, self.cache, self._fresh_row)
        meta = {
            "rid": req.rid,
            "prompt": list(req.prompt),
            "max_new": req.max_new,
            "position": int(self._positions[slot]),
            "remaining": int(self._remaining[slot]),
            "last_tok": int(self._last_tok[slot]),
            "prefill_queue": list(self._prefill_queue[slot]) if slot in self._prefill_queue else None,
            "prefill_open": bool(self._prefill_open.get(slot, False)),
        }
        self._slots[slot] = None
        self._rid_slot.pop(req.rid, None)
        self._prefill_queue.pop(slot, None)
        self._prefill_open.pop(slot, None)
        return state, meta

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is not None]

    def adopt_slot(self, state: Any, meta: Dict[str, Any], req: Optional[Request] = None) -> int:
        """Splice a handed-off slot (from :meth:`extract_slot`, possibly
        round-tripped through ``checkpoint.snapshot``) into the lowest
        free slot and resume its schedule exactly where it stopped.  Pass
        ``req`` when the caller tracks its own request object (the fleet
        worker does); emissions will carry it."""
        slot = self.free_slots()[0]
        self.cache = self._splice(self.cache, state, slot)
        if req is None:
            req = Request(rid=meta["rid"], prompt=list(meta["prompt"]), max_new=meta["max_new"])
        self._slots[slot] = req
        self._positions[slot] = meta["position"]
        self._remaining[slot] = meta["remaining"]
        self._last_tok[slot] = meta["last_tok"]
        self._rid_slot[req.rid] = slot
        if meta.get("prefill_queue") is not None:
            self._prefill_queue[slot] = deque(meta["prefill_queue"])
            self._prefill_open[slot] = meta["prefill_open"]
        return slot

    def abstract_slot_state(self) -> Any:
        """Shape/dtype reference for validating an incoming handoff
        snapshot (``unpack_state(..., abstract=...)``)."""
        return self._fresh_row


class InferenceServer:
    def __init__(self, arch: ArchConfig, params: Any, cfg: Optional[ServeConfig] = None):
        # Per-instance config: a shared mutable default (`cfg=ServeConfig()`
        # evaluated once at import) aliased every no-arg server's state.
        self.cfg = cfg = ServeConfig() if cfg is None else cfg
        self.arch = arch
        self.params = params
        self._rid = itertools.count()
        # Server-side admission queue: requests that have ARRIVED (through
        # the channel, or directly in inline mode) and await a free slot.
        self._pending: deque = deque()
        self.core = DecodeCore(
            arch, params, cfg.slots, cfg.context, cfg.max_prefill, cfg.prefill_chunk
        )
        # The comm hand-off (collective transport): channel + the SAME
        # progress engine as the parcelports, policy from this config.
        self._channel: Optional[CommChannel] = None
        self.engine: Optional[ProgressEngine] = None
        self._inflight: Dict[int, Request] = {}  # rid -> client-side Request
        self._inflight_lock = threading.Lock()
        self._outbox: List[tuple] = []  # (rid, tok, done) batch of one step
        # [requests, responses] dispatched by the engine step that holds
        # the step lock, a new list each step; at unlock each thread keeps
        # its own step's list for its serve.comm span (an executor may pump
        # the engine beside the serve loop)
        self._step_msgs = [0, 0]
        self._own_step = threading.local()
        if cfg.transport in ("collective", "shmem"):
            self._channel = CommChannel(limits=cfg.limits, backend=cfg.transport)
            # step_lock=True: the whole engine step runs behind a try-lock
            # (implemented in `execute`), so a second driver — e.g.
            # AMTExecutor(comm=server) pumping from idle workers — can
            # never interleave dispatches with the serve loop's own step.
            self.engine = ProgressEngine(
                ProgressPolicy.for_config(cfg).variant(step_lock=True),
                self._channel.router(),
                ndevices=1,
            )
            self._step_lock = threading.Lock()
        else:
            assert cfg.transport == "inline", cfg.transport

    # backwards-visible counters/state now owned by the core
    @property
    def cache(self):
        return self.core.cache

    @property
    def steps(self) -> int:
        return self.core.steps

    @property
    def tokens_out(self) -> int:
        return self.core.tokens_out

    # ----------------------------------------------------------------- client
    def submit(self, prompt: List[int], max_new: int = 16) -> Request:
        req = Request(rid=next(self._rid), prompt=list(prompt), max_new=max_new)
        req.submitted_at = time.monotonic()
        if self._channel is None:
            self._pending.append(req)  # legacy direct hand-off
        else:
            with self._inflight_lock:
                self._inflight[req.rid] = req
            # the request crosses the comm layer as bytes; EAGAIN parks it
            # in the channel throttle, retried by the engine step.  The
            # submit stamp rides along: admission reads queue wait from it.
            msg = (req.rid, req.prompt, req.max_new, req.submitted_at)
            self._channel.send_request(encode_msg(msg))
        return req

    # -------------------------------------------- the engine's op adapter
    def execute(self, op: tuple) -> Any:
        """Execute one :class:`ProgressEngine` op against the hand-off
        channel — the serving stack's half of the engine contract (the
        exact analogue of ``LCIParcelport.execute``)."""
        kind = op[0]
        ch = self._channel
        if kind == "reap":
            return ch.reap(op[1].name)
        if kind == "dispatch":
            rec = op[3]
            if rec.op == "send":
                return True  # send completion: slot already recycled
            ch.repost(rec.ctx)  # keep the pre-post depth
            if rec.ctx == "request":
                rid, prompt, max_new, submitted_at = decode_msg(rec.data)
                self._pending.append(Request(rid=rid, prompt=prompt, max_new=max_new, submitted_at=submitted_at))
                self._step_msgs[0] += 1
            else:  # response: a token batch for the client side
                self._apply_response(rec.data)
                self._step_msgs[1] += 1
            return True
        if kind == "progress":
            return ch.progress()
        if kind == "poll":
            return ch.poll()
        if kind == "drain_retries":
            return ch.drain_retries()
        if kind == "step_trylock":
            got = self._step_lock.acquire(blocking=False)
            if got:
                self._step_msgs = [0, 0]
            return got
        if kind == "step_unlock":
            self._own_step.msgs = self._step_msgs
            self._step_lock.release()
            return True
        if kind == "dev_trylock":
            return True
        return False

    def _comm_step(self) -> bool:
        """One canonical engine step over the hand-off channel (drain
        retries → progress → reap → dispatch)."""
        if self.engine is None:
            return False
        self._own_step.msgs = (0, 0)  # stays so if another thread held the lock
        with TraceAnnotation("serve.comm") as span:
            out = run_step(self.engine, self, 0)
            requests, responses = self._own_step.msgs
            span.set_metadata(requests=requests, responses=responses)
        return out

    def _apply_response(self, payload: bytes) -> None:
        """Client side: apply an arrived token batch to its requests.

        A finished request leaves ``_inflight`` only AFTER its final
        token is appended and ``done_event`` is set — ``idle()`` reads
        ``_inflight``, and must never report true while another driver
        thread is still mid-application."""
        now = time.monotonic()
        batch = decode_msg(payload)
        with TraceAnnotation("serve.deliver", tokens=len(batch)):
            for rid, tok, done in batch:
                with self._inflight_lock:
                    req = self._inflight.get(rid)
                if req is None:
                    continue
                if req.first_token_at is None:
                    req.first_token_at = now
                req.out_tokens.append(tok)
                if done:
                    req.done_event.set()
                    with self._inflight_lock:
                        self._inflight.pop(rid, None)

    def _emit(self, req: Request, tok: int, done: bool) -> None:
        """One generated token leaves the server: directly into the
        client's Request (inline), or into this step's outbound batch —
        token completions for all active slots aggregate into ONE response
        message per engine step (§2.2.2 on the serving hot path)."""
        if self._channel is None:
            now = time.monotonic()
            if req.first_token_at is None:
                req.first_token_at = now
            req.out_tokens.append(tok)
            if done:
                req.done_event.set()
        else:
            self._outbox.append((req.rid, tok, done))

    def _flush_outbox(self) -> bool:
        if self._channel is None or not self._outbox:
            return False
        batch, self._outbox = self._outbox, []
        with TraceAnnotation("serve.flush", tokens=len(batch)):
            self._channel.send_response(encode_msg(batch))
        return True

    # ----------------------------------------------------------------- engine
    def _admit(self) -> None:
        for _ in self.core.free_slots():
            if not self._pending:
                return
            self.core.admit(self._pending.popleft(), self._emit)

    def step(self) -> bool:
        """One engine iteration: pump the comm hand-off, admit, batched-
        decode all active slots, flush the token batch back."""
        self._comm_step()
        self._admit()
        if not self.core.step(self._emit):
            if self._flush_outbox():  # e.g. prefill-only finishes
                self._comm_step()
            return False
        self._flush_outbox()
        self._comm_step()
        return True

    # ------------------------------------------------------------- lifecycle
    def pending_requests(self) -> int:
        """Requests admitted server-side but not yet slotted."""
        return len(self._pending)

    def idle(self) -> bool:
        """Nothing slotted, nothing pending, nothing in flight on the
        hand-off channel."""
        if self.core.active() or self._pending:
            return False
        if self._channel is not None and (self._inflight or self._channel.pending_work()):
            return False
        return True

    def run_until_idle(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.step() and self.idle():
                return
