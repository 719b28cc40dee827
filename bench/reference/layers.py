"""Float32 building blocks of the references, in plain ``jax.numpy``.

Every product with a weight goes through :class:`Dense`, which computes in
float32 at ``highest`` matmul precision, or, for the control, with both
operands rounded to int8 first (weights per output channel, activations
per row, symmetric): the int8 path that a later change could be tempted
to take on a TPU v5e.  Everything else is float32 at ``highest``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["Dense", "rms_norm", "mamba2_mixer"]

HI = jax.lax.Precision.HIGHEST
SSD_BLOCK = 64  # block length of the reference scan; any length gives the same sum


def _int8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to a symmetric int8 grid scaled along ``axis``'s max."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


class Dense:
    """``x @ w`` over the last axis of ``x`` and the leading axes of ``w``."""

    def __init__(self, quant: str = "f32"):
        assert quant in ("f32", "int8"), quant
        self.quant = quant

    def __call__(self, x: jax.Array, w: jax.Array, n_in: int = 1) -> jax.Array:
        k = 1
        for d in w.shape[:n_in]:
            k *= d
        w2 = w.reshape(k, -1)
        x2 = x.reshape(x.shape[: x.ndim - n_in] + (k,))
        if self.quant == "int8":
            w2 = _int8(w2, axis=0)
            x2 = _int8(x2, axis=-1)
        y = jnp.matmul(x2, w2, precision=HI)
        return y.reshape(x2.shape[:-1] + w.shape[n_in:])


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _segsum(a: jax.Array) -> jax.Array:
    """(..., T) → (..., T, T): out[..., i, j] = a[j+1] + ... + a[i] for
    j <= i, -inf above the diagonal; summed directly, not as a difference
    of two prefix sums (which loses digits)."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (t,))  # x[..., i, j] = a[i]
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    out = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def _ssd(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, block: int) -> jax.Array:
    """The SSD scan of arXiv:2405.21060, Listing 1 (``ssd_minimal_discrete``):
    y_t = sum_{s<=t} C_t·B_s exp(a_{s+1} + ... + a_t) x_s, for x (S, H, P)
    already scaled by dt, a = dt·A (S, H), one group of B, C (S, N), from a
    zero state.  Quadratic within blocks of ``block`` positions, a
    recurrence over the blocks' states between them."""
    s, h, p = x.shape
    n = b.shape[-1]
    pad = -s % block
    if pad:
        x, a = jnp.pad(x, ((0, pad), (0, 0), (0, 0))), jnp.pad(a, ((0, pad), (0, 0)))
        b, c = jnp.pad(b, ((0, pad), (0, 0))), jnp.pad(c, ((0, pad), (0, 0)))
    nc = x.shape[0] // block
    X = x.reshape(nc, block, h, p)
    A = a.reshape(nc, block, h).transpose(2, 0, 1)  # (H, nc, Q)
    B, C = b.reshape(nc, block, n), c.reshape(nc, block, n)
    a_cs = jnp.cumsum(A, axis=-1)
    # 1. within blocks: the quadratic (attention-like) form
    L = jnp.exp(_segsum(A))  # (H, nc, Q, Q)
    y_diag = jnp.einsum("cln,csn,hcls,cshp->clhp", C, B, L, X, precision=HI)
    # 2. each block's state from its own inputs
    decay = jnp.exp(a_cs[..., -1:] - a_cs)  # (H, nc, Q)
    states = jnp.einsum("csn,hcs,cshp->chpn", B, decay, X, precision=HI)
    # 3. the recurrence between blocks
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], axis=0)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cs[..., -1], ((0, 0), (1, 0)))))  # (H, nc+1, nc+1)
    states = jnp.einsum("hzc,chpn->zhpn", chunk_decay, states, precision=HI)[:-1]
    # 4. each block's output from the state it starts from
    y_off = jnp.einsum("cln,chpn,hcl->clhp", C, states, jnp.exp(a_cs), precision=HI)
    return (y_diag + y_off).reshape(nc * block, h, p)[:s]


def mamba2_mixer(dense: Dense, p, u: jax.Array, arch) -> jax.Array:
    """The Mamba2 block (arXiv:2405.21060, Fig. 6 and §6-7) over one
    sequence ``u`` (S, D), from a zero state.

    in_proj gives [z, xBC, dt]; xBC passes a causal depthwise conv1d (with
    bias) and SiLU and splits into x (heads × head dim), B and C (one group
    of ``d_state``); dt = softplus(dt + dt_bias), A = -exp(A_log).  The
    scan h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_tᵀ, y_t = C_t h_t + D x_t is
    the paper's own minimal SSD (:func:`_ssd`), in blocks of 64.  The
    output is RMSNorm(y · SiLU(z)) (the gated norm) then out_proj."""
    d_in = arch["ssm_expand"] * arch["d_model"]
    hd, n, k = arch["ssm_head_dim"], arch["ssm_state"], arch["ssm_conv"]
    h = d_in // hd
    s = u.shape[0]
    zxbcdt = dense(u, p["in_proj"])
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in : 2 * d_in + 2 * n], zxbcdt[:, 2 * d_in + 2 * n :]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv = sum(padded[i : i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(s, h, hd)
    b, c = xbc[:, d_in : d_in + n], xbc[:, d_in + n :]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (S, H)
    a = -jnp.exp(p["A_log"])  # (H,)
    y = _ssd(x * dt[:, :, None], dt * a, b, c, SSD_BLOCK)
    y = y + x * p["D"][None, :, None]
    y = rms_norm(y.reshape(s, d_in) * jax.nn.silu(z), p["norm_w"], arch["norm_eps"])
    return dense(y, p["out_proj"])
