"""Public jit'd wrappers around the Pallas kernels.

The models call these through :func:`kernel_mode`, read from
``REPRO_KERNELS`` at trace time: ``"pallas"`` (the default on a TPU
backend), ``"xla"`` (the reference lowering; the default elsewhere, and
the explicit reference switch on the chip) or ``"pallas-interpret"``
(the kernels in the Pallas interpreter; CPU only).  Numerics contracts
are pinned by tests against :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .moe_gmm import grouped_matmul
from .ref import attention_ref, grouped_matmul_ref, ssd_chunk_ref
from .ssd_scan import ssd_chunk_kernel

__all__ = [
    "flash_attention",
    "ssd_chunk_kernel",
    "grouped_matmul",
    "attention",
    "expert_ffn_matmul",
    "kernel_mode",
    "reference_lowering",
]


KERNEL_MODES = ("pallas", "pallas-interpret", "xla")

_ctx = threading.local()


@contextlib.contextmanager
def reference_lowering():
    """Trace the enclosed model code with the ``xla`` reference lowering,
    whatever ``REPRO_KERNELS`` says.  The train step differentiates under
    it: the kernels are forward-only (no VJP) and GSPMD cannot partition
    a Mosaic call."""
    prev = getattr(_ctx, "reference", False)
    _ctx.reference = True
    try:
        yield
    finally:
        _ctx.reference = prev


def kernel_mode() -> str:
    """'pallas' (default on TPU) | 'xla' (default elsewhere) | 'pallas-interpret'.

    Interpret mode on a TPU backend is an error, not a silent slow path."""
    if getattr(_ctx, "reference", False):
        return "xla"
    mode = os.environ.get("REPRO_KERNELS", "")
    on_tpu = jax.default_backend() == "tpu"
    if not mode:
        return "pallas" if on_tpu else "xla"
    if mode not in KERNEL_MODES:
        raise ValueError(f"REPRO_KERNELS={mode!r}; expected one of {KERNEL_MODES}")
    if on_tpu and mode == "pallas-interpret":
        raise RuntimeError(
            "REPRO_KERNELS=pallas-interpret on a TPU backend: the chip would run the "
            "Pallas interpreter; use 'pallas', or 'xla' for the reference lowering"
        )
    return mode


def attention(q, k, v, *, causal=True, window=0, chunk=0) -> jax.Array:
    mode = kernel_mode()
    if mode == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window, chunk=chunk)
    if mode == "pallas-interpret":
        return flash_attention(q, k, v, causal=causal, window=window, chunk=chunk, interpret=True)
    return attention_ref(q, k, v, causal=causal, window=window, chunk=chunk)


def expert_ffn_matmul(x, w) -> jax.Array:
    mode = kernel_mode()
    if mode == "pallas":
        return grouped_matmul(x, w)
    if mode == "pallas-interpret":
        return grouped_matmul(x, w, interpret=True)
    return grouped_matmul_ref(x, w)
