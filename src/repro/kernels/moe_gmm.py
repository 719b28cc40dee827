"""Grouped (per-expert) matmul kernel for MoE FFN batches (Pallas TPU).

Computes (E, C, D) × (E, D, F) → (E, C, F): every expert's token queue
against its own weight matrix.  Grid ``(E, C/bc, F/bf, D/bd)`` with a
float32 VMEM accumulator; the contraction axis is the innermost
("arbitrary") grid dimension so each (bc × bf) output tile accumulates
across D-tiles while Q/W tiles stream HBM→VMEM.  Block sizes default to
MXU-native 128×128×512.

This is the hot-spot of the MoE channel mixer; the einsum in
:mod:`repro.models.moe` is the reference lowering used by the dry-run.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["grouped_matmul"]


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(di == nd - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret")
)
def grouped_matmul(
    x: jax.Array,  # (E, C, D)
    w: jax.Array,  # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w.shape[-1]
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert c % block_c == 0 and f % block_f == 0 and d % block_d == 0, (
        f"dims ({c},{d},{f}) must divide blocks ({block_c},{block_d},{block_f})"
    )
    nd = d // block_d
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nd=nd),
        grid=(e, c // block_c, f // block_f, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e_, c_, f_, d_: (e_, c_, d_)),
            pl.BlockSpec((1, block_d, block_f), lambda e_, c_, f_, d_: (e_, d_, f_)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f), lambda e_, c_, f_, d_: (e_, c_, f_)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)
