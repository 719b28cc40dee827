"""The yardstick's work functions against counts made by hand or from the
served parameter tree itself."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.roofline import work  # noqa: E402
from bench.roofline.peaks import peaks  # noqa: E402


def _arch(name):
    """The program's own configuration, as a dict of its sizes."""
    import dataclasses

    from repro.configs import get_config

    c = get_config(name)
    return {f.name: getattr(c, f.name) for f in dataclasses.fields(c) if f.name != "source"}


@pytest.mark.parametrize("name", ["zamba2-1.2b", "mamba2-130m"])
def test_forward_matmul_params_match_the_served_tree(name):
    from repro.configs.base import ArchConfig

    from bench.weights import param_shapes

    a = _arch(name)
    shapes = param_shapes(ArchConfig(**a))
    size = lambda x: int(np.prod(x.shape))  # noqa: E731
    per_token = sum(size(shapes["layers"]["ssm"][k]) for k in ("in_proj", "out_proj"))
    if "shared_block" in shapes:
        sb = shapes["shared_block"]
        uses = len(range(0, a["n_layers"], a["attn_every"]))
        per_token += uses * (sum(size(v) for v in sb["attn"].values()) + sum(size(v) for v in sb["ffn"].values()))
    assert work.forward_matmul_params(a) == per_token


def test_ssd_chunk_by_hand():
    # one sequence of 128 positions, 2 heads, chunk 64, P = N = 64
    w = work.ssd_chunk(1, 2, 1, 128, 64, 64, 64)
    pairs = 64 * 65 / 2
    assert w["flops"] == 2 * 2 * (2 * pairs * 64 + 2 * pairs * 64 + 2 * 64 * 64 * 64)
    assert w["bytes"] == 2 * 128 * 4 + 2 * 128 * 64 * 2 + 2 * 128 * 64 * 2 + 2 * 128 * 64 * 2 + 2 * 2 * 64 * 64 * 4


def test_decode_live_bytes_counts_live_positions_not_the_ring():
    a = _arch("zamba2-1.2b")
    w = 2.34e9
    empty = work.decode_live_bytes(a, 0, 24, w)
    full = work.decode_live_bytes(a, 24 * 2048, 24, w)
    kv_row = 2 * 32 * 64 * 2  # keys and values of one position, bf16
    assert full - empty == pytest.approx(7 * 24 * 2048 * kv_row)
    state = 64 * 64 * 64 + 3 * (4096 + 128)
    assert empty == pytest.approx(w + 2 * 38 * 24 * state * 2 + 7 * 24 * kv_row)


def test_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
