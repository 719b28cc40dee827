"""The train step: microbatched grad accumulation, remat, AdamW.

``make_train_step(cfg, hp, tcfg)`` returns a pure ``(state, batch) →
(state, metrics)`` suitable for ``jax.jit`` with sharded state/batch.
Distribution is GSPMD-driven: parameters/activations carry logical-axis
annotations (:mod:`repro.sharding`), the gradient all-reduce over the
data axes and any tensor/expert-parallel collectives appear in the
lowered HLO (inspected by the dry-run/roofline).

Microbatching: the global batch splits into ``microbatches`` slices
scanned sequentially with f32 gradient accumulation — the activation-
memory lever of §Perf.  Optional int8 gradient compression with error
feedback lives in :mod:`repro.train.grad_sync` (explicit-DP mode).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.ops import reference_lowering
from ..models import model as model_lib
from ..optim import OptHParams, adamw_init, adamw_update
from ..sharding.logical import shard

__all__ = ["TrainConfig", "TrainState", "init_train_state", "make_train_step"]


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: str = "dots"  # 'none' | 'full' | 'dots' | 'dots_no_batch'
    grad_sync: str = "auto"  # 'auto' (GSPMD) | 'int8_ef' (explicit compression)
    # Which packer the explicit-DP wire hand-off uses: 'host' = the numpy
    # reference loop, 'device' = the fused Pallas quantize+pack kernel
    # (bit-identical wire bytes; see grad_sync.make_packer).
    grad_pack: str = "host"

    def __post_init__(self):
        assert self.grad_pack in ("host", "device"), self.grad_pack

    def variant(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


TrainState = Dict[str, Any]  # {"params", "opt", "step", ["ef"]}


def init_train_state(rng: jax.Array, cfg: ArchConfig, tcfg: Optional[TrainConfig] = None) -> TrainState:
    params = model_lib.init_params(rng, cfg)
    state: TrainState = {
        "params": params,
        "opt": adamw_init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if tcfg is not None and tcfg.grad_sync == "int8_ef":
        state["ef"] = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def _split_micro(batch: Dict[str, jax.Array], m: int) -> Dict[str, jax.Array]:
    """(B, ...) → (m, B/m, ...) for scanning."""

    def sp(x):
        b = x.shape[0]
        assert b % m == 0, f"batch {b} % microbatches {m} != 0"
        return x.reshape(m, b // m, *x.shape[1:])

    return jax.tree.map(sp, batch)


def make_train_step(
    cfg: ArchConfig,
    hp: OptHParams,
    tcfg: TrainConfig = TrainConfig(),
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    def loss(params, mb):
        with reference_lowering():
            total, metrics = model_lib.loss_fn(params, cfg, mb, remat=tcfg.remat)
        return total, metrics

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        params = state["params"]
        m = tcfg.microbatches
        if m == 1:
            (l, metrics), grads = grad_fn(params, batch)
        else:
            micro = _split_micro(batch, m)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (l, met), g = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + l), met

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, l), mets = jax.lax.scan(acc, (g0, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: (g / m).astype(jnp.float32), grads)
            l = l / m
            metrics = jax.tree.map(lambda x: x[-1], mets)
        if tcfg.grad_sync == "int8_ef":
            from .grad_sync import compress_grads_int8_ef

            grads, new_ef = compress_grads_int8_ef(grads, state["ef"])
        new_params, new_opt, opt_metrics = adamw_update(grads, state["opt"], params, hp)
        new_state: TrainState = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        if tcfg.grad_sync == "int8_ef":
            new_state["ef"] = new_ef
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss_mean"] = l
        return new_state, metrics

    return train_step
