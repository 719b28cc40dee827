"""Flash attention (forward) as a Pallas TPU kernel.

TPU-native adaptation: Q/K/V stream HBM→VMEM in (block_q × head_dim) /
(block_k × head_dim) tiles sized for the MXU (multiples of 128 on the lane
axis); the online-softmax running max / denominator / accumulator live in
VMEM scratch across the ``kv`` grid steps.  Grid layout
``(batch, q_heads, num_q_blocks, num_kv_blocks)`` with the kv axis
sequential ("arbitrary") and all others parallel.

Supports causal, sliding-window (``window > 0``) and chunked-local
(``chunk > 0``) masking, and GQA via a head-index map (kv head =
q head // group).  Causal/window/chunk block pairs that are fully masked
are skipped entirely (`pl.when` on the block indices), so SWA costs
O(S·window) — the same contract as the model-level reference.

Validated against :mod:`repro.kernels.ref` in ``interpret=True`` mode on
CPU (this container has no TPU); the BlockSpecs are the TPU deployment
configuration.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

NEG_INF = -1e30


def _attn_kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bk, d)
    v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, 1, bq, d)
    m_scr,  # (bq, 128) f32 scratch — running max
    l_scr,  # (bq, 128) f32 scratch — running denominator
    acc_scr,  # (bq, d) f32 scratch — weighted-value accumulator
    *,
    scale: float,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
    causal: bool,
    window: int,
    chunk: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        if chunk > 0:
            mask &= (kpos // chunk) == (qpos // chunk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # skip fully-masked block pairs
    live = True
    if causal:
        live = jnp.asarray(k_start <= q_start + block_q - 1)
    if window > 0:
        live &= jnp.asarray(k_start + block_k - 1 > q_start - window)
    if chunk > 0:
        # chunk ranges of the two blocks must overlap
        live &= jnp.asarray(k_start // chunk <= (q_start + block_q - 1) // chunk)
        live &= jnp.asarray((k_start + block_k - 1) // chunk >= q_start // chunk)
    if isinstance(live, bool):
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,  # (B, S, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, "GQA requires n_heads % n_kv_heads == 0"
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, "seq must divide block size"
    nq, nk = sq // block_q, skv // block_k
    scale = 1.0 / math.sqrt(d)

    # layout: heads-major so a (block, d) tile is contiguous per (b, h)
    qt = q.transpose(0, 2, 1, 3)  # (B, H, S, D)
    kt = k.transpose(0, 2, 1, 3)  # (B, KV, S, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
        causal=causal,
        window=window,
        chunk=chunk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # back to (B, S, H, D)
