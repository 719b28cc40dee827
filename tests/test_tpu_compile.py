"""The main path's Pallas kernels compile for a TPU v5e at real widths,
and the decode program updates its donated cache in place there.

Nothing runs: each test compiles one kernel or program for a chip that is
described (``v5e:2x2``, device 0), not attached, and checks the compiled
program.  This is what interpret mode and the CPU backend cannot show:
block shapes off the (8, 128) tiling and unaligned dynamic slices are
refused here, and the copies kept are the TPU compiler's.  The
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

from _cache_copies import whole_stack_copies
from repro.configs import ARCHS
from repro.core.comm import wire
from repro.kernels import grad_pack
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_chunk_kernel
from repro.models import decode_step, init_cache, init_params


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


@pytest.mark.parametrize(
    "name, slots, context",
    [
        ("mamba2-130m", 128, 512),  # the served cells' decode program
        ("tinyllama-1.1b", 8, 256),  # GQA ring
        ("minicpm3-4b", 8, 256),  # MLA latent cache
        ("whisper-large-v3", 8, 256),  # read-only cross K/V beside the ring
        ("zamba2-1.2b", 8, 256),  # SSM stack beside the shared-attention cache
    ],
)
def test_decode_step_updates_donated_cache_in_place(one_chip, name, slots, context):
    """With the cache donated, the compiled decode program copies no whole
    stacked cache that it writes (``ssm``, ``kv``): each layer's slice is
    written back into the buffer that came in.  Real widths: a smoke
    config's stack is small enough that the compiler stages all of it in
    on-chip memory, which is a copy of another kind."""
    cfg = ARCHS[name]
    place = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots, context)))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    positions = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda p, t, pos, c: decode_step(p, cfg, t, pos, c), donate_argnums=(3,))
    hlo = fn.lower(params, tokens, positions, cache).compile().as_text()
    assert whole_stack_copies(hlo, cache) == []
