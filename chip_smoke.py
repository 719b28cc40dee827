#!/usr/bin/env python3
"""Bring-up smoke test on a TPU: the quickest proof that the system starts
on the chip.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the sharded train step, 4 chips

One chip (the default) runs two phases:

1. **serve** — zamba2-1.2b at its published widths, weights from
   ``init_params(PRNGKey(seed))``, served through ``InferenceServer`` over
   the default ``collective`` transport, built by the same
   :func:`repro.launch.serve.build_server` as ``python -m
   repro.launch.serve``: 8 requests of 128-token seeded prompts,
   ``max_new=16``, 4 slots, a 512-token context, ``max_prefill=128``.
   It counts the Mosaic kernels (``tpu_custom_call``) in the compiled
   prefill and decode step, and compares the first request's prefill
   logits on the kernel path with the XLA reference lowering.
2. **grad-pack** — ``pack_grads_fused(mode="pallas")`` at the 4 MiB point
   of ``benchmarks/grad_sync_bench.py``; its wire bytes must equal the
   host reference ``pack_grads_q8`` byte for byte.

``--four-chips`` runs only the sharded train step of zamba2-1.2b (batch
8, seq 512, 5 steps) on a ``(2, 2)`` ``("data", "model")`` mesh of the
host's four chips, against the unsharded forward loss of the same batch
on one chip.

Any failed check, and any exception, exits non-zero.  With no TPU the
script exits non-zero before it does anything: it never runs on the CPU.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "zamba2-1.2b"

# serve phase
REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS, CONTEXT, MAX_PREFILL = 8, 128, 16, 4, 512, 128
# Kernel-vs-XLA prefill logits, bf16 model: max |Δ| over the vocabulary
# must stay within LOGIT_TOL of the reference's largest |logit|.  The two
# lowerings round to bf16 at different points; on the CPU the gap grows
# about as sqrt(depth) (0.6% at 2 layers, 2.1% at 18, d_model 512), which
# puts 38 layers near 3%.  A wrong mask or decay is off by O(1).
LOGIT_TOL = 0.08
# four-chip phase
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 5
# Sharded step-0 loss against the one-chip forward loss (absolute, nats).
LOSS_TOL = 0.02


def require_tpu(count: int):
    """The device this run measures; exits unless JAX sees ``count`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform {devs[0].platform!r}); refusing to run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found {len(devs)}")
    return devs[:count]


def kernel_calls(compiled) -> Counter:
    """Mosaic kernels in a compiled program, by the jitted kernel's name."""
    names = Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"jit\((\w+)\)/pallas_call", line)
            names[m.group(1) if m else "?"] += 1
    return names


def serve_phase(arch, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import reference_lowering
    from repro.launch.serve import build_server, serve_prompts
    from repro.models import init_cache, init_params, prefill

    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(seed), arch))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"model: {arch.name} params={n_params} ({n_params / 1e9:.3f}B) dtype={arch.dtype} "
          f"init={time.perf_counter() - t0:.1f}s")

    prompts = np.random.default_rng(seed).integers(0, arch.vocab_size, (REQUESTS, PROMPT_LEN)).tolist()
    server = build_server(arch, params, slots=SLOTS, context=CONTEXT, max_prefill=MAX_PREFILL)
    core = server.core

    # the server's own jitted entry points, compiled ahead of the run
    first = {"tokens": jnp.asarray(prompts[:1], jnp.int32)}
    t0 = time.perf_counter()
    pre = core._prefill_one.lower(params, first, init_cache(arch, 1, CONTEXT)).compile()
    dec = core._decode.lower(
        params, jnp.zeros((SLOTS, 1), jnp.int32), jnp.zeros((SLOTS,), jnp.int32), core.cache
    ).compile()
    compile_s = time.perf_counter() - t0
    k_pre, k_dec = kernel_calls(pre), kernel_calls(dec)
    print(f"compile: prefill+decode {compile_s:.1f}s")
    print(f"kernels: prefill tpu_custom_call={sum(k_pre.values())} {dict(k_pre)}; "
          f"decode tpu_custom_call={sum(k_dec.values())} {dict(k_dec)}")
    if not k_pre:
        raise SystemExit("chip_smoke: the compiled prefill holds no tpu_custom_call")
    if "flash_attention" not in k_pre:
        print(f"note: prefill attention took the XLA path at sq={PROMPT_LEN}")

    # warm-up: one short request compiles what the AOT step did not
    # (cache splice, argmax), so the timed run below is steady state
    t0 = time.perf_counter()
    warm = serve_prompts(server, prompts[:1], max_new=2)
    warm_s = time.perf_counter() - t0
    if not all(r.done_event.is_set() for r in warm):
        raise SystemExit("chip_smoke: the warm-up request did not finish")
    steps0, tokens0 = server.steps, server.tokens_out

    t0 = time.perf_counter()
    reqs = serve_prompts(server, prompts, max_new=MAX_NEW)
    run_s = time.perf_counter() - t0
    done = [r for r in reqs if r.done_event.is_set() and len(r.out_tokens) == MAX_NEW]
    tokens = server.tokens_out - tokens0
    print(f"serve: requests={len(done)}/{len(reqs)} tokens_out={tokens} "
          f"engine_steps={server.steps - steps0} transport={server.cfg.transport}")
    print(f"time: warm-up (first request, compiles left) {warm_s:.2f}s; "
          f"run of {len(reqs)} requests {run_s:.3f}s wall (host clock, compile excluded)")
    if len(done) != len(reqs):
        raise SystemExit("chip_smoke: requests left unfinished")

    # the first request's prefill logits: kernel path vs the XLA reference
    def prefill_logits():
        # a fresh function per call: the lowering is read at trace time, and
        # jit would reuse the first trace for the same function object
        return jax.jit(lambda p, b, c: prefill(p, arch, b, c)[0])(params, first, init_cache(arch, 1, CONTEXT))

    lk = prefill_logits()
    with reference_lowering():
        lx = prefill_logits()
    lk, lx = np.asarray(lk, np.float32), np.asarray(lx, np.float32)
    diff, scale = float(np.max(np.abs(lk - lx))), float(np.max(np.abs(lx)))
    print(f"logits: max|kernel - xla|={diff:.6g} max|xla|={scale:.6g} "
          f"rel={diff / scale:.6g} (tolerance {LOGIT_TOL} relative) "
          f"argmax_equal={int(lk.argmax()) == int(lx.argmax())} finite={bool(np.isfinite(lk).all())}")
    if not np.isfinite(lk).all() or diff > LOGIT_TOL * scale:
        raise SystemExit("chip_smoke: kernel-path logits disagree with the XLA reference")


def grad_pack_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.grad_sync_bench import CLAIM_POINT, grad_tree
    from repro.kernels.grad_pack import pack_grads_fused
    from repro.train.grad_sync import pack_grads_q8

    tree = grad_tree(*CLAIM_POINT, seed=seed)
    ef = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)
    want, ef_host = pack_grads_q8(tree, ef)
    got, ef_dev = pack_grads_fused(tree, ef, mode="pallas")
    a, b = np.frombuffer(want, np.uint8), np.frombuffer(got, np.uint8)
    n_diff = int(np.count_nonzero(a != b)) if a.size == b.size else -1
    ef_diff = sum(
        int(np.count_nonzero(np.asarray(h) != np.asarray(d)))
        for h, d in zip(jax.tree.leaves(ef_host), jax.tree.leaves(ef_dev))
    )
    grad_mib = sum(x.size * 4 for x in jax.tree.leaves(tree)) / 2**20
    print(f"grad-pack: d={CLAIM_POINT[0]} layers={CLAIM_POINT[1]} grads={grad_mib:.2f}MiB "
          f"wire={len(got)}B pallas==host {got == want} (bytes differing: {n_diff}; "
          f"ef elements differing: {ef_diff})")
    if got != want:
        raise SystemExit("chip_smoke: pallas grad-pack wire bytes differ from the host reference")


def four_chip_phase(arch, devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_rules, make_test_mesh
    from repro.models import init_params, loss_fn
    from repro.optim import OptHParams
    from repro.sharding.logical import use_rules

    toks = jnp.asarray(np.random.default_rng(seed).integers(0, arch.vocab_size, (TRAIN_BATCH, TRAIN_SEQ)), jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    key = jax.random.PRNGKey(seed)

    # one chip, unsharded: the forward loss of the same batch
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(key, arch)
    ref_loss = float(jax.jit(lambda p, b: loss_fn(p, arch, b)[0])(params, batch))
    del params
    print(f"one chip ({devices[0].device_kind}): forward loss={ref_loss:.6f} ({time.perf_counter() - t0:.1f}s)")

    mesh = make_test_mesh((2, 2))
    hp = OptHParams(lr_peak=1e-3, warmup_steps=1, total_steps=TRAIN_STEPS)
    with use_rules(make_rules(mesh)), mesh:
        step, init_state = sharded_train_step(arch, hp, mesh, batch)
        t0 = time.perf_counter()
        state = init_state(key)
        losses = []
        for _ in range(TRAIN_STEPS):
            state, met = step(state, batch)
            losses.append(float(met["loss"]))
        jax.block_until_ready(state)
    print(f"mesh {dict(mesh.shape)} on {len(devices)} chips: losses={[round(x, 6) for x in losses]} "
          f"({time.perf_counter() - t0:.1f}s incl. compile)")
    for d in devices:
        print(f"  device {d.id}: bytes_in_use={d.memory_stats()['bytes_in_use']}")
    d0 = abs(losses[0] - ref_loss)
    print(f"step-0 loss vs one-chip forward: |Δ|={d0:.6g} (tolerance {LOSS_TOL}); "
          f"loss fell {losses[0]:.6f} -> {losses[-1]:.6f}")
    if not np.isfinite(losses).all() or d0 > LOSS_TOL:
        raise SystemExit("chip_smoke: sharded step-0 loss disagrees with the one-chip forward")
    if not losses[-1] < losses[0]:
        raise SystemExit("chip_smoke: the sharded train step did not lower the loss")


def sharded_train_step(arch, hp, mesh, batch):
    """The sharded train step and a state initialiser placed on ``mesh``
    (under the active logical rules), as the dry run lays them out."""
    import jax

    from repro.sharding.logical import current_rules
    from repro.sharding.params import batch_specs, opt_specs, param_specs, tree_shardings
    from repro.train import TrainConfig, init_train_state, make_train_step

    rules = current_rules()
    shapes = jax.eval_shape(lambda k: init_train_state(k, arch), jax.random.PRNGKey(0))
    spec = {
        "params": param_specs(shapes["params"], rules),
        "opt": opt_specs(shapes["opt"], shapes["params"], rules, zero=True, mesh=mesh),
        "step": jax.sharding.PartitionSpec(),
    }
    state_sh = tree_shardings(mesh, spec, shapes)
    batch_sh = tree_shardings(mesh, batch_specs(batch, rules), batch)
    step = jax.jit(
        make_train_step(arch, hp, TrainConfig(remat="full")), in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None), donate_argnums=(0,),
    )
    init_state = jax.jit(lambda k: init_train_state(k, arch), out_shardings=state_sh)
    return step, init_state


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on a (2, 2) mesh of four chips")
    args = ap.parse_args()

    devices = require_tpu(4 if args.four_chips else 1)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    arch = get_config(ARCH)
    if args.four_chips:
        four_chip_phase(arch, devices, args.seed)
    else:
        serve_phase(arch, args.seed)
        grad_pack_phase(args.seed)
    import jax

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
