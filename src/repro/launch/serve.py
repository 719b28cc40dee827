"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Runs the continuous-batching engine on a (smoke) model with a synthetic
request stream submitted from multiple client threads, and prints
latency/throughput stats — the serving-side end-to-end driver.  The
request/response hand-off rides the shared comm layer (``--transport
collective``, the default): requests and token batches cross
``CommInterface`` verbs, driven by the same ``ProgressEngine`` as the
parcelport study; ``--transport inline`` runs the legacy direct path;
``--transport shmem`` rides the one-sided put backend.

``--workers N`` (N > 1) scales the model tier out into the ISSUE 7
fleet: one router, N sharded-KV workers, per-worker channels over one
shared group — same math, same request stream, distributed serving.
``--prefill-chunk C`` turns on chunked prefill (prompts cross the wire
as C-token pieces interleaved with decode).  ``--full`` serves the
published widths instead of the smoke preset; ``--context`` and
``--max-prefill`` size the KV ring and the prompt bucket.
"""
from __future__ import annotations

import argparse
import threading
import time
from typing import Any, List, Sequence

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..configs.base import ArchConfig
from ..models import init_params
from ..serve import Fleet, FleetConfig, InferenceServer, Request, ServeConfig
from .compile_cache import enable_compile_cache

__all__ = ["build_server", "serve_prompts", "main"]


def build_server(
    arch: ArchConfig,
    params: Any,
    *,
    slots: int,
    context: int,
    max_prefill: int,
    transport: str = "collective",
    workers: int = 1,
    prefill_chunk: int = 0,
):
    """The single-host server, or the router+fleet tier for ``workers > 1``."""
    if workers > 1:
        return Fleet(
            arch, params,
            FleetConfig(
                workers=workers, slots=slots, context=context, max_prefill=max_prefill,
                transport=transport, prefill_chunk=prefill_chunk,
            ),
        )
    return InferenceServer(
        arch, params,
        ServeConfig(
            slots=slots, context=context, max_prefill=max_prefill, transport=transport,
            prefill_chunk=prefill_chunk,
        ),
    )


def serve_prompts(server, prompts: Sequence[List[int]], max_new: int, clients: int = 1) -> List[Request]:
    """Submit ``prompts`` from ``clients`` threads while this thread drives
    the engine loop; returns the requests once the server is idle."""
    reqs: List[Request] = []
    lock = threading.Lock()

    def client(mine: Sequence[List[int]]) -> None:
        for prompt in mine:
            r = server.submit(list(prompt), max_new=max_new)
            with lock:
                reqs.append(r)
            time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(prompts[i::clients],)) for i in range(clients)]
    for t in threads:
        t.start()
    # engine loop = the shared progress engine (paper §3.3.4, explicit
    # driving): each step pumps the comm hand-off and the batched decode
    while any(t.is_alive() for t in threads) or not server.idle():
        if not server.step():
            time.sleep(1e-3)
    for t in threads:
        t.join()
    server.run_until_idle()
    return reqs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--context", type=int, default=256)
    ap.add_argument("--max-prefill", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument(
        "--transport", choices=("collective", "shmem", "inline"), default="collective"
    )
    ap.add_argument(
        "--workers", type=int, default=1,
        help="model workers; >1 runs the router+fleet tier (slots shard across workers)",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunked prefill: prompt piece size in tokens (0 = single-shot prefill)",
    )
    args = ap.parse_args()

    enable_compile_cache()
    arch = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(jax.random.PRNGKey(0), arch)
    server = build_server(
        arch, params, slots=args.slots, context=args.context, max_prefill=args.max_prefill,
        transport=args.transport, workers=args.workers, prefill_chunk=args.prefill_chunk,
    )
    rng = np.random.default_rng(0)
    per = args.requests // args.clients
    prompts = [
        rng.integers(0, arch.vocab_size, size=args.prompt_len).tolist()
        for _ in range(per * args.clients)
    ]
    t0 = time.monotonic()
    reqs = serve_prompts(server, prompts, args.max_new, args.clients)
    dt = time.monotonic() - t0
    done = [r for r in reqs if r.done_event.is_set()]
    ttft = [r.first_token_at - r.submitted_at for r in done if r.first_token_at]
    tier = f"fleet(workers={args.workers})" if args.workers > 1 else "single-host"
    extra = ""
    if args.workers > 1:
        extra = f" eagain={server.eagain_events}"
        server.close()
    else:
        extra = f" host_syncs={server.core.host_syncs} compiles={server.core.compiles}"
    print(
        f"requests={len(done)}/{len(reqs)} engine_steps={server.steps} "
        f"tokens={server.tokens_out} throughput={server.tokens_out/dt:.1f} tok/s "
        f"ttft_p50={np.median(ttft)*1e3:.1f}ms transport={args.transport} "
        f"tier={tier}{extra}"
    )
    return 0 if len(done) == len(reqs) else 1


if __name__ == "__main__":
    raise SystemExit(main())
