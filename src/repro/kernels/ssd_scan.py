"""Mamba2 SSD intra-chunk kernel (Pallas TPU).

The SSD chunked algorithm splits into (a) an embarrassingly parallel
intra-chunk quadratic block — the compute hot spot, O(S·Q) MXU work — and
(b) a tiny sequential inter-chunk state recurrence.  This kernel computes
(a): for each (batch, head, chunk) grid cell it produces

* ``y_diag``  — the causal intra-chunk output ((C·Bᵀ ⊙ L) · X),
* ``state``   — the chunk's contribution to the running SSM state
  (Σ_t exp(A_last − A_t) · b_t ⊗ x_t),
* ``y_off`` is then a small batched matmul applied in JAX after the
  inter-chunk scan (:func:`repro.models.ssm.ssd_chunked` shape contract).

Grid ``(B, H, num_chunks)``; blocks keep the full (Q × P) / (Q × N) tiles
in VMEM (Q=64..128, P=64, N=128 → ≤128 KiB per operand, MXU-aligned lanes).
GQA-style B/C groups are resolved by the index map (head → group), so the
broadcast never materializes in HBM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ssd_chunk_kernel"]


def _ssd_kernel(a_ref, x_ref, b_ref, c_ref, y_ref, s_ref):
    # a: (1,1,1,1,Q)  x: (1,1,1,Q,P)  b,c: (1,1,1,Q,N)
    a = a_ref[0, 0, 0].astype(jnp.float32)  # (1,Q) — one lane row
    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q,P)
    b = b_ref[0, 0, 0].astype(jnp.float32)  # (Q,N)
    c = c_ref[0, 0, 0].astype(jnp.float32)  # (Q,N)
    q = a.shape[-1]
    li = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = lj <= li
    # Prefix sums of A·dt as f32 matmuls against the causal mask: Mosaic
    # has no cumsum, and the column form is needed next to the row form.
    tri = causal.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    exact = jax.lax.Precision.HIGHEST
    acs_col = jax.lax.dot_general(tri, a, nt, precision=exact, preferred_element_type=jnp.float32)  # (Q,1)
    acs_row = jax.lax.dot_general(a, tri, nt, precision=exact, preferred_element_type=jnp.float32)  # (1,Q)
    # L[i,j] = exp(acs_i - acs_j) for j <= i else 0
    L = jnp.exp(jnp.where(causal, acs_col - acs_row, -jnp.inf))
    g = jax.lax.dot_general(c, b, nt, preferred_element_type=jnp.float32)
    y = jax.lax.dot(g * L, x, preferred_element_type=jnp.float32)  # (Q,P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    decay = jnp.exp(acs_row[:, q - 1 :] - acs_col)  # (Q,1)
    bw = b * decay  # (Q,N)
    state = jax.lax.dot(x.T, bw, preferred_element_type=jnp.float32)  # (P,N)
    s_ref[0, 0, 0] = state.astype(s_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_kernel(
    a_dt: jax.Array,  # (B, H, nc, Q)   A·dt per step
    x: jax.Array,  # (B, H, nc, Q, P) pre-discretized inputs (x·dt)
    b: jax.Array,  # (B, G, nc, Q, N)
    c: jax.Array,  # (B, G, nc, Q, N)
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y_diag (B,H,nc,Q,P), chunk_states (B,H,nc,P,N))."""
    bsz, h, nc, q = a_dt.shape
    p = x.shape[-1]
    g_, n = b.shape[1], b.shape[-1]
    rep = h // g_
    y_shape = jax.ShapeDtypeStruct((bsz, h, nc, q, p), x.dtype)
    s_shape = jax.ShapeDtypeStruct((bsz, h, nc, p, n), jnp.float32)
    return pl.pallas_call(
        _ssd_kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, q), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda b_, h_, c_: (b_, h_ // rep, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda b_, h_, c_: (b_, h_ // rep, c_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
        ],
        out_shape=[y_shape, s_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(a_dt.reshape(bsz, h, nc, 1, q), x, b, c)
