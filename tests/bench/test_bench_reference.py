"""The float32 references agree with the program on the smoke configs:
the whole-sequence forward, and prefill then decode through the cache.
Both run in float32 at ``highest`` precision here, so they differ only by
the order of float32 sums: 1e-4 of the largest logit is 10x what was seen."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.reference.run import forward  # noqa: E402
from bench.weights import make_params  # noqa: E402

TOL = 1e-4
CASES = [("mamba2-130m", "mamba2")]


def _setup(name):
    from repro.configs import get_smoke_config

    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    arch = get_smoke_config(cfg["registry"]).variant(dtype="float32")
    arch_dict = {f.name: getattr(arch, f.name) for f in dataclasses.fields(arch) if f.name != "source"}
    params = make_params(arch, cfg["init"], 2**31 + 99)
    toks = np.random.default_rng(5).integers(0, arch.vocab_size, 40)
    return cfg, arch, arch_dict, params, toks


@pytest.mark.parametrize("name,family", CASES)
def test_forward_matches_program(name, family):
    from repro.models import forward_train

    cfg, arch, arch_dict, params, toks = _setup(name)
    with jax.default_matmul_precision("highest"):
        prog, _ = forward_train(params, arch, {"tokens": jnp.asarray(toks[None])})
    ref = forward(family, arch_dict, params, toks)
    assert np.abs(np.asarray(prog[0]) - ref).max() <= TOL * np.abs(ref).max()


@pytest.mark.parametrize("name,family", CASES)
def test_prefill_then_decode_matches_program(name, family):
    from repro.models import decode_step, init_cache, prefill

    cfg, arch, arch_dict, params, toks = _setup(name)
    ref = forward(family, arch_dict, params, toks)
    with jax.default_matmul_precision("highest"):
        cache = init_cache(arch, 1, 64)
        logits, cache = prefill(params, arch, {"tokens": jnp.asarray(toks[None, :30])}, cache)
        got = [np.asarray(logits[0, -1])]
        for t in range(30, 39):
            logits, cache = decode_step(params, arch, jnp.asarray(toks[None, t : t + 1]), jnp.asarray([t]), cache)
            got.append(np.asarray(logits[0, 0]))
    assert np.abs(np.stack(got) - ref[29:39]).max() <= TOL * np.abs(ref).max()


def test_padding_after_a_sequence_changes_none_of_its_logits():
    cfg, arch, arch_dict, params, toks = _setup("mamba2-130m")
    a = forward("mamba2", arch_dict, params, toks[:25])
    b = forward("mamba2", arch_dict, params, np.concatenate([toks[:25], np.zeros(15, toks.dtype)]))
    assert np.abs(a - b[:25]).max() <= TOL * np.abs(a).max()


def test_int8_control_is_a_different_computation():
    cfg, arch, arch_dict, params, toks = _setup("mamba2-130m")
    a = forward("mamba2", arch_dict, params, toks)
    b = forward("mamba2", arch_dict, params, toks, quant="int8")
    d = np.abs(a - b).max() / np.abs(a).max()
    assert 1e-4 < d < 0.5
