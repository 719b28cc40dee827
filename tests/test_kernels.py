"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True on CPU; BlockSpecs are the TPU deployment config)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import grouped_matmul
from repro.kernels.ref import attention_ref, grouped_matmul_ref, ssd_chunk_ref
from repro.kernels.ssd_scan import ssd_chunk_kernel

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return 5e-5 if dtype == jnp.float32 else 4e-2


# ------------------------------------------------------------ flash attention
FLASH_CASES = [
    # (B, S, H, KV, D, causal, window, chunk, dtype, bq, bk)
    (2, 256, 4, 2, 64, True, 0, 0, jnp.float32, 128, 128),
    (1, 512, 4, 4, 128, True, 0, 0, jnp.float32, 128, 128),
    (2, 256, 8, 2, 64, True, 64, 0, jnp.float32, 128, 128),
    (2, 256, 4, 2, 64, True, 0, 128, jnp.float32, 128, 128),
    (1, 256, 8, 2, 64, False, 0, 0, jnp.float32, 128, 128),
    (1, 256, 4, 2, 128, True, 0, 0, jnp.bfloat16, 128, 128),
    (1, 128, 2, 2, 64, True, 0, 0, jnp.float32, 64, 64),
    (1, 384, 6, 3, 64, True, 128, 0, jnp.float32, 128, 128),
    (2, 128, 2, 1, 32, True, 0, 0, jnp.float32, 64, 64),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_sweep(case):
    b, s, h, kv, d, causal, window, chunk, dtype, bq, bk = case
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, chunk=chunk,
                          block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, chunk=chunk)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < _tol(dtype), f"{case}: err={err}"


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from([64, 128]),
    st.sampled_from([(2, 1), (4, 2), (4, 4)]),
    st.sampled_from([128, 256]),
    st.booleans(),
)
def test_flash_attention_property(d, heads, s, causal):
    h, kv = heads
    ks = jax.random.split(jax.random.PRNGKey(d * s + h), 3)
    q = jax.random.normal(ks[0], (1, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, kv, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-5


# ------------------------------------------------------------------ SSD chunk
SSD_CASES = [
    # (B, H, G, nc, Q, P, N)
    (2, 4, 2, 3, 64, 64, 128),
    (1, 2, 1, 2, 128, 64, 64),
    (1, 8, 8, 1, 64, 32, 128),
    (2, 2, 1, 4, 32, 64, 32),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_chunk_kernel_sweep(case):
    B, H, G, NC, Q, P, N = case
    ks = jax.random.split(jax.random.PRNGKey(sum(case)), 4)
    a = -jnp.abs(jax.random.normal(ks[0], (B, H, NC, Q))) * 0.1
    x = jax.random.normal(ks[1], (B, H, NC, Q, P))
    bb = jax.random.normal(ks[2], (B, G, NC, Q, N)) * 0.3
    cc = jax.random.normal(ks[3], (B, G, NC, Q, N)) * 0.3
    y, st_ = ssd_chunk_kernel(a, x, bb, cc, interpret=True)
    rep = H // G
    for b_ in range(B):
        for h_ in range(H):
            for c_ in range(NC):
                yr, sr = ssd_chunk_ref(
                    x[b_, h_, c_][None, :, None, :],
                    a[b_, h_, c_][None, :, None],
                    bb[b_, h_ // rep, c_][None, :, None, :],
                    cc[b_, h_ // rep, c_][None, :, None, :],
                )
                assert float(jnp.max(jnp.abs(y[b_, h_, c_] - yr[0, :, 0]))) < 1e-4
                assert float(jnp.max(jnp.abs(st_[b_, h_, c_] - sr[0, 0]))) < 1e-4


def test_ssd_model_path_matches_kernel_path():
    """ssd_chunked (model) == kernel-backed path, end to end."""
    import os

    from repro.models.ssm import ssd_chunked

    B, S, H, G, P, N, Q = 2, 64, 4, 1, 32, 64, 16
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    a_dt = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.1
    b = jax.random.normal(ks[2], (B, S, G, N), jnp.float32) * 0.3
    c = jax.random.normal(ks[3], (B, S, G, N), jnp.float32) * 0.3
    os.environ["REPRO_KERNELS"] = "xla"
    y1, s1 = ssd_chunked(x, a_dt, b, c, Q)
    os.environ["REPRO_KERNELS"] = "pallas-interpret"
    try:
        y2, s2 = ssd_chunked(x, a_dt, b, c, Q)
    finally:
        os.environ["REPRO_KERNELS"] = "xla"
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-4
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-4


# -------------------------------------------------------------- grouped matmul
GMM_CASES = [
    (4, 256, 512, 384, jnp.float32, 128, 128, 256),
    (2, 128, 128, 128, jnp.float32, 128, 128, 128),
    (8, 128, 256, 128, jnp.bfloat16, 128, 128, 256),
    (1, 512, 1024, 256, jnp.float32, 128, 128, 512),
]


@pytest.mark.parametrize("case", GMM_CASES)
def test_grouped_matmul_sweep(case):
    e, c, d, f, dtype, bc, bf, bd = case
    ks = jax.random.split(jax.random.PRNGKey(e + c + d), 2)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype) * 0.05
    out = grouped_matmul(x, w, block_c=bc, block_f=bf, block_d=bd, interpret=True)
    ref = grouped_matmul_ref(x, w)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < (1e-4 if dtype == jnp.float32 else 5e-2), f"{case}: {err}"


def test_flash_attention_equals_model_attention_core():
    """Model q-chunked scan path and Pallas kernel agree through the
    attention entry point (kernel_mode switch)."""
    import os

    from repro.models.attention import _attention_core

    B, S, H, KV, D = 1, 256, 4, 2, 64
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    os.environ["REPRO_KERNELS"] = "xla"
    ref = _attention_core(q, k, v, pos, pos, "full", 0)
    os.environ["REPRO_KERNELS"] = "pallas-interpret"
    try:
        out = _attention_core(q, k, v, pos, pos, "full", 0)
    finally:
        os.environ["REPRO_KERNELS"] = "xla"
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-5


@pytest.mark.parametrize("env,backend,want", [
    ("", "tpu", "pallas"),
    ("", "cpu", "xla"),
    ("xla", "tpu", "xla"),
    ("pallas-interpret", "cpu", "pallas-interpret"),
    ("pallas-interpret", "tpu", RuntimeError),
    ("pallsa", "cpu", ValueError),
])
def test_kernel_mode_resolution(monkeypatch, env, backend, want):
    """Interpret mode is refused on a TPU backend; a misspelt mode is an
    error rather than a silent fall-through to the reference lowering."""
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNELS", env)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if isinstance(want, str):
        assert ops.kernel_mode() == want
    else:
        with pytest.raises(want):
            ops.kernel_mode()
