"""Float32 reference of a pure Mamba2 language model (arXiv:2405.21060).

Embedding, ``n_layers`` pre-norm residual Mamba2 blocks (RMSNorm, then the
mixer of :func:`bench.reference.layers.mamba2_mixer`), a final RMSNorm and
the output head (the embedding itself where ``tie_embeddings``).

Departures from the published model, each the served configuration's:
one B/C group; random weights drawn from the seed.
"""
from __future__ import annotations

import jax

from .layers import Dense, mamba2_mixer, rms_norm

__all__ = ["layer_fns"]


def layer_fns(arch, dense: Dense):
    """The jitted pieces a forward pass is made of, for
    :func:`bench.reference.run.forward`: ``(block(layer_index), head)``."""
    eps = arch["norm_eps"]

    @jax.jit
    def mamba(lp, h):
        return h + mamba2_mixer(dense, lp["ssm"], rms_norm(h, lp["ln1"], eps), arch)

    @jax.jit
    def head(params, h):
        w = params["embed"] if arch.get("tie_embeddings") else params["lm_head"]
        return dense(rms_norm(h, params["ln_f"], eps), w.T)

    def blocks(i):
        return [("layer", mamba)]

    return blocks, head
