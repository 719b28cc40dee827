"""Teacher-forced forward of a reference over whole sequences, layer by
layer, one sequence at a time, so that it fits beside nothing else."""
from __future__ import annotations

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Dense

__all__ = ["forward"]


@functools.lru_cache(maxsize=None)
def _fns(family: str, arch_json: str, quant: str):
    mod = importlib.import_module(f"bench.reference.{family}")
    return mod.layer_fns(json.loads(arch_json), Dense(quant))


def forward(family: str, arch, params, tokens: np.ndarray, quant: str = "f32") -> np.ndarray:
    """Logits (S, V) in float32 for one token sequence (S,).  ``params`` is
    the float32 parameter tree; ``family`` names ``bench/reference/<family>.py``."""
    blocks, head = _fns(family, json.dumps(arch, sort_keys=True), quant)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)]
        for i in range(arch["n_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            for kind, fn in blocks(i):
                h = fn(lp if kind == "layer" else params[kind], h)
        return np.asarray(head(params, h))
