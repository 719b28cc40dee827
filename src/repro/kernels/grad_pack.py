"""Fused gradient quantize+pack kernel (Pallas TPU) — the device data plane.

The host grad-sync path does three separate walks over the gradient tree:
a per-leaf ``tree.map`` for error-feedback + int8 quantize, a
``tree.transpose`` to split the results, and a host-side pack loop that
serializes leaf-by-leaf.  This module fuses all of it into ONE
``pallas_call`` over HBM→VMEM tiles of a single flat f32 buffer:

    error-feedback add  +  per-tensor int8 quantize  +  pack

producing one flat device buffer — tile-padded int8 payload, per-tensor
f32 scales, u32 offset table — that goes to the wire via a single
``jax.device_get`` with the versioned header from
:mod:`repro.core.comm.wire` prepended.  The receiver's
:func:`unpack_grads_fused` (or :func:`repro.train.grad_sync.unpack_grads`,
same format) rebuilds the pytree.

Kernel shape: leaves are flattened, zero-padded to :data:`wire.PACK_TILE`
elements, and concatenated; each tile enters the kernel as one whole
``(8, 128)`` block, and a scalar-prefetched ``seg_ids`` table maps
each tile to its leaf.  Grid ``(2, n_tiles)`` makes two sequential passes:

* phase 0 — per-tile ``max(|g+ef|)`` folded into a per-leaf running max
  held in VMEM scratch (scratch persists across grid steps);
* phase 1 — ``scale = max(maxabs, 1e-12)/127`` per leaf, quantize the
  tile, emit the int8 payload tile + the f32 error-feedback tile, and on
  the last tile flush the scales vector.

The payload/ef output index map is ``(i, j) -> (i*j, 0, 0)``: every phase-0
step aliases block 0, so each output block's visits form one consecutive
run (Pallas's revisit rule) and the real writes all happen in phase 1.

Parity contract: in every mode the wire bytes are bit-identical to the
host reference :func:`repro.train.grad_sync.pack_grads_q8` — max
reductions are exact, the elementwise f32 add/div/round/clip pipeline is
IEEE, and numpy/XLA/Mosaic all round half-to-even.  Tier-1 asserts this
at every size in the Fig-3 ladder (``tests/test_grad_pack.py``).

Three-mode ladder as in :mod:`repro.kernels.ops`: ``xla`` reference
(segment-max formulation), ``pallas-interpret`` (CPU CI), ``pallas``
(TPU).
"""
from __future__ import annotations

import functools
import struct
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.comm import wire

__all__ = ["pack_grads_fused", "unpack_grads_fused", "packed_nbytes"]

TILE = wire.PACK_TILE
ROWS = TILE // 128  # one (8, 128) f32 vreg tile per grid step

# Error-feedback update, in every path (host numpy / XLA / Mosaic):
#
#     r  = g32 / scale
#     q  = clip(round(r), -127, 127)
#     ef = (r - q) * scale
#
# NOT ``g32 - q*scale``: backends contract multiply-then-subtract into one
# fma (single rounding) while numpy rounds twice, which makes the EF state
# differ in the last ulp and lets multi-step wire bytes drift.  In the
# ``(r - q) * scale`` form the multiply comes last — there is no
# mul-feeding-add pattern to contract — so each op rounds once,
# identically, everywhere.  The scale likewise uses an explicit
# reciprocal multiply (see _RECIP127): XLA strength-reduces
# division-by-constant into reciprocal multiplication, which is 1 ulp off
# IEEE division for some inputs.  For the same reason the kernel divides
# with :func:`_div_rn`: Mosaic lowers f32 ``a / b`` to ``a * recip(b)``.
_RECIP127 = float(np.float32(1.0) / np.float32(127.0))


def _div_rn(a, b):
    """IEEE ``a / b`` (f32, round to nearest even) for ``b > 0`` normal,
    by restoring long division on the 24-bit significands in int32, so the
    result is the same on every backend.  Subnormal ``a`` and quotients
    below the normal range give zero, as XLA's CPU and TPU backends flush."""
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    ia, ib = i32(a), i32(b)
    ea = (ia >> 23) & 0xFF
    ma, mb = (ia & 0x7FFFFF) | 0x800000, (ib & 0x7FFFFF) | 0x800000
    lt = (ma < mb).astype(jnp.int32)
    exp = ea - ((ib >> 23) & 0xFF) + 127 - lt
    rem, q = ma << lt, jnp.zeros_like(ia)  # mb <= rem < 2 mb: the first bit is 1
    for _ in range(25):  # 24 significand bits and a guard bit
        bit = (rem >= mb).astype(jnp.int32)
        rem = (rem - bit * mb) << 1
        q = (q << 1) | bit
    q = (q >> 1) + ((q & 1) & ((rem != 0) | ((q >> 1) & 1)).astype(jnp.int32))
    mag = jax.lax.bitcast_convert_type(((exp - 1) << 23) + q, jnp.float32)  # a carry bumps exp
    return jnp.where((ea == 0) | (exp <= 0), np.float32(0.0), jnp.where(a < 0, -mag, mag))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _pack_kernel(n_tiles, seg_ref, g_ref, ef_ref, payload_ref, scales_ref, ef_out_ref, maxabs_ref):
    phase = pl.program_id(0)
    j = pl.program_id(1)
    s = seg_ref[j]
    g32 = g_ref[0] + ef_ref[0]  # (ROWS, 128) f32 — the fused EF add

    @pl.when((phase == 0) & (j == 0))
    def _init():
        maxabs_ref[...] = jnp.zeros_like(maxabs_ref)

    @pl.when(phase == 0)
    def _max_pass():
        m = jnp.max(jnp.max(jnp.abs(g32), axis=0, keepdims=True), axis=1, keepdims=True)
        maxabs_ref[s] = jnp.maximum(maxabs_ref[s], m)

    @pl.when(phase == 1)
    def _quant_pass():
        scale = jnp.maximum(maxabs_ref[s], 1e-12) * _RECIP127  # (1, 128), lanes equal
        r = _div_rn(g32, scale)
        q = jnp.clip(jnp.round(r), -127, 127)
        payload_ref[0] = q.astype(jnp.int32).astype(jnp.int8)
        ef_out_ref[0] = (r - q) * scale

        @pl.when(j == n_tiles - 1)
        def _flush_scales():
            scales_ref[...] = jnp.maximum(maxabs_ref[...], 1e-12) * _RECIP127


def _pallas_pack(g_tiles, ef_tiles, seg_ids, n_leaves, *, interpret):
    """Tiles go in as ``(n_tiles, ROWS, 128)`` so every block is one whole
    (8, 128) vreg tile; the per-leaf running max lives in a leading-dim
    indexed ``(n_leaves, 1, 128)`` scratch (lanes hold copies), so the
    kernel needs no dynamic lane slice."""
    n_tiles = g_tiles.shape[0]
    tile = lambda i, j, seg: (j, 0, 0)
    out_tile = lambda i, j, seg: (i * j, 0, 0)
    whole = lambda i, j, seg: (0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(2, n_tiles),
        in_specs=[pl.BlockSpec((1, ROWS, 128), tile), pl.BlockSpec((1, ROWS, 128), tile)],
        out_specs=[
            pl.BlockSpec((1, ROWS, 128), out_tile),
            pl.BlockSpec((n_leaves, 1, 128), whole),
            pl.BlockSpec((1, ROWS, 128), out_tile),
        ],
        scratch_shapes=[pltpu.VMEM((n_leaves, 1, 128), jnp.float32)],
    )
    q, scales, ef_out = pl.pallas_call(
        functools.partial(_pack_kernel, n_tiles),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, ROWS, 128), jnp.int8),
            jax.ShapeDtypeStruct((n_leaves, 1, 128), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, ROWS, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(seg_ids, g_tiles.reshape(n_tiles, ROWS, 128), ef_tiles.reshape(n_tiles, ROWS, 128))
    return q, scales[:, 0, 0], ef_out


def _xla_pack(g_tiles, ef_tiles, seg_ids, n_leaves):
    """Reference lowering: segment-max over per-tile maxima, then the same
    elementwise quantize pipeline as the kernel."""
    tiles = g_tiles + ef_tiles
    tile_max = jnp.max(jnp.abs(tiles), axis=1)
    maxabs = jax.ops.segment_max(tile_max, seg_ids, num_segments=n_leaves)
    # tile-less (empty) leaves come back as the segment identity (-inf);
    # the host convention for an empty leaf is maxabs == 0.
    maxabs = jnp.maximum(maxabs, 0.0)
    scale = jnp.maximum(maxabs, 1e-12) * _RECIP127
    st = scale[seg_ids][:, None]
    r = tiles / st
    q = jnp.clip(jnp.round(r), -127, 127).astype(jnp.int8)
    ef_out = (r - q.astype(jnp.float32)) * st
    return q, scale, ef_out


# ---------------------------------------------------------------------------
# Host-facing wrapper with per-(treedef, shapes, mode) jit cache
# ---------------------------------------------------------------------------

_CACHE: dict = {}


def _kernel_mode() -> str:
    from .ops import kernel_mode

    return kernel_mode()


def packed_nbytes(tree: Any) -> int:
    """Wire size of :func:`pack_grads_fused`'s output for ``tree``."""
    specs = [wire.leaf_spec(leaf, quantized=True) for leaf in jax.tree.leaves(tree)]
    payload = sum(wire.padded_nelems(s.nelems) for s in specs)
    return wire.grad_header_bytes(specs) + 8 * len(specs) + payload


def _build(treedef, avals, mode):
    specs = [wire.LeafSpec(wire.dtype_code(d), tuple(int(x) for x in s), int(np.prod(s, dtype=np.int64))) for s, d in avals]
    header = wire.encode_grad_header(wire.KIND_Q8, specs)
    offs = wire.q8_offsets(specs)
    padded = [wire.padded_nelems(s.nelems) for s in specs]
    n_tiles = sum(padded) // TILE
    n_leaves = len(specs)
    seg_ids = np.repeat(np.arange(n_leaves, dtype=np.int32), [p // TILE for p in padded])
    offs_bytes = struct.pack(f"<{n_leaves}I", *offs)

    if n_tiles == 0:
        # Every leaf is empty (or the tree is): nothing for the kernel to
        # do.  Scales follow the maxabs==0 convention; payload is empty.
        scales = struct.pack(f"<{n_leaves}f", *([float(np.float32(np.float32(1e-12) * np.float32(_RECIP127)))] * n_leaves))
        data = header + offs_bytes + scales

        def run_empty(leaves, efs):
            new_ef = [jnp.zeros(s.shape, jnp.float32) for s in specs]
            return data, jax.tree.unflatten(treedef, new_ef)

        return run_empty

    seg_dev = jnp.asarray(seg_ids)
    offs_dev = jnp.asarray(np.frombuffer(offs_bytes, dtype=np.uint8))

    starts = np.cumsum([0] + padded[:-1]) if padded else []

    def flatten(leaves, efs):
        # dynamic_update_slice into one zeroed buffer: ~6x faster than the
        # naive per-leaf pad + concatenate on XLA CPU, and the zero fill
        # doubles as the tile padding.
        g_buf = jnp.zeros((n_tiles * TILE,), jnp.float32)
        e_buf = jnp.zeros((n_tiles * TILE,), jnp.float32)
        for (shape, _d), start, g, e in zip(avals, starts, leaves, efs):
            if int(np.prod(shape, dtype=np.int64)) == 0:
                continue
            g_buf = jax.lax.dynamic_update_slice(
                g_buf, g.astype(jnp.float32).reshape(-1), (int(start),)
            )
            e_buf = jax.lax.dynamic_update_slice(
                e_buf, e.reshape(-1).astype(jnp.float32), (int(start),)
            )
        return g_buf.reshape(n_tiles, TILE), e_buf.reshape(n_tiles, TILE)

    def assemble(q, scales, ef_out):
        body = jnp.concatenate(
            [
                offs_dev,
                jax.lax.bitcast_convert_type(scales.reshape(-1), jnp.uint8).reshape(-1),
                jax.lax.bitcast_convert_type(q.reshape(-1), jnp.uint8),
            ]
        )
        ef_flat = ef_out.reshape(-1)
        new_ef, cur = [], 0
        for s, pad_n in zip(specs, padded):
            new_ef.append(ef_flat[cur : cur + s.nelems].reshape(s.shape))
            cur += pad_n
        return body, new_ef

    @jax.jit
    def run(leaves, efs):
        g_tiles, ef_tiles = flatten(leaves, efs)
        if mode == "xla":
            q, scales, ef_out = _xla_pack(g_tiles, ef_tiles, seg_dev, n_leaves)
        else:
            q, scales, ef_out = _pallas_pack(
                g_tiles, ef_tiles, seg_dev, n_leaves, interpret=(mode == "pallas-interpret")
            )
        return assemble(q, scales, ef_out)

    def run_host(leaves, efs):
        body, new_ef = run(leaves, efs)
        data = b"".join([header, memoryview(np.asarray(jax.device_get(body)).data)])
        return data, jax.tree.unflatten(treedef, new_ef)

    return run_host


def pack_grads_fused(tree: Any, ef: Any, mode: Optional[str] = None) -> Tuple[bytes, Any]:
    """Fused device pack: returns ``(wire_bytes, new_ef_tree)`` with wire
    bytes bit-identical to :func:`repro.train.grad_sync.pack_grads_q8`.
    ``mode`` defaults to the session's :func:`~repro.kernels.ops.kernel_mode`."""
    mode = mode or _kernel_mode()
    leaves, treedef = jax.tree.flatten(tree)
    ef_leaves = jax.tree.leaves(ef)
    avals = tuple((tuple(int(d) for d in np.shape(g)), np.dtype(getattr(g, "dtype", np.float32))) for g in leaves)
    key = (treedef, avals, mode)
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = _build(treedef, avals, mode)
    return fn(leaves, ef_leaves)


def unpack_grads_fused(data, like: Any) -> Any:
    """Rebuild the dequantized (f32) gradient pytree from
    :func:`pack_grads_fused` wire bytes — the receiver-side twin."""
    buf = memoryview(data)
    kind, specs, off = wire.parse_grad_header(buf)
    if kind != wire.KIND_Q8:
        raise ValueError(f"expected KIND_Q8 wire payload, got kind {kind}")
    n = len(specs)
    off += 4 * n
    scales = np.frombuffer(buf, dtype=np.float32, count=n, offset=off)
    off += 4 * n
    leaves: List[Any] = []
    for s, scale in zip(specs, scales):
        q = np.frombuffer(buf, dtype=np.int8, count=s.nelems, offset=off)
        leaves.append(jnp.asarray(q.astype(np.float32) * scale).reshape(s.shape))
        off += wire.padded_nelems(s.nelems)
    return jax.tree.unflatten(jax.tree.structure(like), leaves)
