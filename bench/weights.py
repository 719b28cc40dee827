"""Seeded weights in the layout the program serves, made by the benchmark.

The program under test receives these weights as input; it makes none of
its own.  The shapes and dtypes are read from the program's parameter
tree (``jax.eval_shape`` of ``init_params``: the interface, nothing is
computed), and every leaf is drawn from ``--seed`` by the law that the
configuration file names for the leaf's key under ``init``.  The plain
reference regenerates the same values from the same seed with the same
function, so it takes nothing that the program made.

All leaves are drawn in one jitted call on the device.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_key", "param_shapes", "make_params"]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed below 2**64, one to one (two 32-bit words)."""
    seed = int(seed) % 2**64
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def param_shapes(arch) -> Any:
    from repro.models import init_params

    return jax.eval_shape(lambda k: init_params(k, arch), jax.random.PRNGKey(0))


def _leaf(key: jax.Array, rule: Dict[str, Any], shape, stacked: bool) -> jax.Array:
    law = rule["law"]
    if law == "normal":
        return rule["std"] * jax.random.normal(key, shape, jnp.float32)
    if law == "normal_fan_in":
        per_layer = shape[1:] if stacked else shape
        fan_in = math.prod(per_layer[a] for a in rule["axes"])
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) / math.sqrt(fan_in)
    if law == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, rule["low"], rule["high"])
    if law == "log_uniform":
        # log of a value uniform in [low, high] (Mamba2's A = -exp(A_log))
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, rule["low"], rule["high"]))
    if law == "softplus_inv_log_uniform":
        # Mamba2's dt bias: softplus(bias) is log-uniform in [low, high]
        dt = jnp.exp(
            jax.random.uniform(key, shape, jnp.float32, math.log(rule["low"]), math.log(rule["high"]))
        )
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init law {law!r}")


def make_params(arch, init: Dict[str, Dict[str, Any]], seed: int, dtype=None) -> Any:
    """Every leaf of the program's parameter tree, drawn from ``seed``.

    ``dtype=None`` keeps each leaf's serving dtype; ``jnp.float32`` gives
    the reference's copy of the same values (drawn in f32, rounded to the
    serving dtype, then widened, so both sides hold identical numbers).
    """
    shapes = param_shapes(arch)
    paths = jax.tree_util.tree_leaves_with_path(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    def draw(key):
        out = []
        for i, (path, sd) in enumerate(paths):
            name = path[-1].key
            if name not in init:
                raise KeyError(f"configuration has no init law for parameter {jax.tree_util.keystr(path)}")
            stacked = path[0].key == "layers"
            v = _leaf(jax.random.fold_in(key, i), init[name], sd.shape, stacked).astype(sd.dtype)
            out.append(v if dtype is None else v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(draw)(seed_key(seed)))
