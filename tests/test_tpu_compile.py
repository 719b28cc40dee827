"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles one kernel for a chip that is described
(``v5e:2x2``, device 0), not attached, and checks that the Mosaic call is
in the program.  This is what interpret mode cannot show: block shapes off
the (8, 128) tiling and unaligned dynamic slices are refused here.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.comm import wire
from repro.kernels import grad_pack
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_chunk_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_attention_compiles_at_zamba2_widths(one_chip):
    # zamba2-1.2b's shared block: 32 heads of 64, SWA window 4096, seq 512
    qkv = jax.ShapeDtypeStruct((1, 512, 32, 64), jnp.bfloat16, sharding=one_chip)
    hlo = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096), qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_ssd_chunk_kernel_compiles_at_zamba2_widths(one_chip):
    # zamba2-1.2b's Mamba2 blocks: 64 heads, P=64, N=64, chunk Q=64, 1 group
    b, h, g, nc, q, p, n = 1, 64, 1, 8, 64, 64, 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    hlo = _compile(
        ssd_chunk_kernel,
        sds((b, h, nc, q), jnp.float32),
        sds((b, h, nc, q, p), jnp.bfloat16),
        sds((b, g, nc, q, n), jnp.bfloat16),
        sds((b, g, nc, q, n), jnp.bfloat16),
    )
    assert "tpu_custom_call" in hlo


def test_grad_pack_kernel_compiles_at_4mib(one_chip):
    # the 4 MiB point of benchmarks/grad_sync_bench.py: d=88, 12 layers of
    # wqkv, wo, w1, w2, ln1, ln2 (72 leaves, 4.26 MiB of f32 gradients)
    d = 88
    layer = [(d, 3 * d), (d, d), (d, 4 * d), (4 * d, d), (d,), (d,)]
    sizes = [int(np.prod(s)) for s in layer] * 12
    tiles = [wire.padded_nelems(s) // grad_pack.TILE for s in sizes]
    n_tiles, n_leaves = sum(tiles), len(sizes)
    f32 = jax.ShapeDtypeStruct((n_tiles, grad_pack.TILE), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((n_tiles,), jnp.int32, sharding=one_chip)
    hlo = _compile(
        lambda g, e, s: grad_pack._pallas_pack(g, e, s, n_leaves, interpret=False), f32, f32, seg
    )
    assert "tpu_custom_call" in hlo
