"""Device data plane (ISSUE 9): fused quantize+pack kernel parity, the
versioned binary wire format, copy discipline, and staged aggregation.

The tentpole contract is BIT parity, not tolerance: the fused kernel's
wire bytes (xla and pallas-interpret lowerings) must equal the host
reference ``pack_grads_q8`` byte for byte — same header, same offset
table, same scales, same tile-padded int8 payload — at every size in the
Fig-3 ladder, for f32 and bf16 leaves, ragged shapes, and across
multi-step error-feedback evolution.
"""
import pickle
import struct
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import wire
from repro.kernels.grad_pack import (
    pack_grads_fused,
    packed_nbytes,
    unpack_grads_fused,
)
from repro.train.grad_sync import (
    compress_grads_int8_ef,
    pack_grads,
    pack_grads_q8,
    unpack_grads,
)

# Fig 3 size ladder (same points as benchmarks/latency.py CROSSOVER_SIZES):
# per size S, a tree whose quantized payload is about S bytes.
FIG3_SIZES = (512, 4096, 8192, 16384, 32768, 65536)


def _tree_for_size(nelems: int, seed: int = 0):
    """A ragged multi-leaf tree totalling ``nelems`` elements."""
    rng = np.random.default_rng(seed)
    a = max(1, nelems // 2)
    b = max(1, nelems // 3)
    c = max(0, nelems - a - b)
    tree = {
        "w": jnp.asarray(rng.standard_normal(a), jnp.float32),
        "b": jnp.asarray(rng.standard_normal(b) * 1e-3, jnp.float32),
        "v": jnp.asarray(rng.standard_normal(c), jnp.float32),
    }
    ef = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)
    return tree, ef


def _zeros_ef(tree):
    return jax.tree.map(lambda x: jnp.zeros(np.shape(x), jnp.float32), tree)


# --------------------------------------------------------------- wire format


def test_grad_header_roundtrip():
    arrs = [np.zeros((3, 4), np.float32), np.zeros((0,), np.int8),
            np.zeros((), np.float32), np.zeros((2, 1, 5), np.int32)]
    specs = [wire.leaf_spec(a) for a in arrs]
    hdr = wire.encode_grad_header(wire.KIND_RAW, specs)
    kind, got, off = wire.parse_grad_header(hdr)
    assert kind == wire.KIND_RAW and off == len(hdr)
    assert [(s.shape, s.dtype, s.nbytes) for s in got] == [
        (a.shape, a.dtype, a.nbytes) for a in arrs
    ]


def test_msg_codec_roundtrip_and_container_fidelity():
    msgs = [
        (3, [1, 2, 3], 16),
        ("new", 7, [5, 6], True, 8),
        [("eagain", 0, 3), (4, 17, False)],
        (),
        {"k": b"\x00\xff", "v": -1.5},
        None,
    ]
    for m in msgs:
        out = wire.decode_msg(wire.encode_msg(m))
        assert out == m
        assert type(out) is type(m)  # list stays list, tuple stays tuple
    with pytest.raises(TypeError):
        wire.encode_msg(object())


def test_pack_grads_matches_old_pickle_decoded_values():
    """Satellite 1: the binary format carries exactly what the old pickle
    stream carried — decoding both yields the same leaf values/dtypes."""
    rng = np.random.default_rng(3)
    tree = {
        "w": (jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
              jnp.asarray(rng.integers(-100, 100, (8,)), jnp.int8)),
        "b": jnp.asarray(rng.standard_normal((5,)).astype(np.float16)),
    }
    # the pre-ISSUE-9 wire: pickle of (leaf ndarray list)
    old = pickle.dumps([np.asarray(l) for l in jax.tree.leaves(tree)])
    new = pack_grads(tree)
    got = unpack_grads(new, tree)
    for g, o in zip(jax.tree.leaves(got), pickle.loads(old)):
        assert np.asarray(g).dtype == o.dtype
        np.testing.assert_array_equal(np.asarray(g), o)
    # int8 leaves stay int8 on the wire (the 4x reduction) and the binary
    # format beats pickle's overhead
    assert len(new) < len(old)


def test_pack_grads_rejects_garbage():
    with pytest.raises(ValueError):
        wire.parse_grad_header(b"\x00" * 16)


# ------------------------------------------------- fused kernel: bit parity


@pytest.mark.parametrize("size", FIG3_SIZES)
def test_fused_pack_bit_parity_fig3_ladder(size):
    """Wire bytes from the fused kernel == host reference, bit for bit, at
    every Fig-3 ladder size, in both CI lowerings."""
    tree, ef = _tree_for_size(size, seed=size)
    want, ef_host = pack_grads_q8(tree, ef)
    for mode in ("xla", "pallas-interpret"):
        got, ef_dev = pack_grads_fused(tree, ef, mode=mode)
        assert got == want, f"mode={mode} size={size}: wire bytes differ"
        for eh, ed in zip(jax.tree.leaves(ef_host), jax.tree.leaves(ef_dev)):
            np.testing.assert_array_equal(np.asarray(ed), np.asarray(eh))
    assert len(want) == packed_nbytes(tree)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_pack_bit_parity_dtypes_ragged(dtype):
    rng = np.random.default_rng(11)
    dt = jnp.dtype(dtype)
    tree = {
        "attn": (jnp.asarray(rng.standard_normal((33, 17)), dt),
                 jnp.asarray(rng.standard_normal((129,)), dt)),
        "mlp": [jnp.asarray(rng.standard_normal((7, 3, 5)), dt),
                jnp.asarray(rng.standard_normal((1,)), dt)],
    }
    ef = _zeros_ef(tree)
    want, _ = pack_grads_q8(tree, ef)
    for mode in ("xla", "pallas-interpret"):
        got, _ = pack_grads_fused(tree, ef, mode=mode)
        assert got == want, f"mode={mode} dtype={dtype}"


def test_fused_pack_multistep_ef_bit_parity():
    """10 steps of EF evolution: feeding each path its OWN ef state keeps
    the wire bytes identical every step (ef states must therefore agree
    bitwise too — drift anywhere would desynchronize the streams)."""
    rng = np.random.default_rng(23)
    tree0 = {"w": jnp.asarray(rng.standard_normal((640,)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((9,)) * 1e-4, jnp.float32)}
    ef_h, ef_x, ef_p = _zeros_ef(tree0), _zeros_ef(tree0), _zeros_ef(tree0)
    for step in range(10):
        g = jax.tree.map(
            lambda x: x * np.float32(1.0 + 0.1 * step) + np.float32(0.01 * step), tree0
        )
        want, ef_h = pack_grads_q8(g, ef_h)
        got_x, ef_x = pack_grads_fused(g, ef_x, mode="xla")
        got_p, ef_p = pack_grads_fused(g, ef_p, mode="pallas-interpret")
        assert got_x == want, f"xla step {step}"
        assert got_p == want, f"pallas-interpret step {step}"


def test_fused_ef_equivalent_to_compress_grads_int8_ef():
    """Same quantizer, same EF semantics, over 10 steps.  The in-jit path
    computes EF as fma-contracted ``g - q*scale`` while the fused path
    uses ``(r - q) * scale`` (see grad_pack.py's _RECIP127 note): the
    1-ulp EF difference can flip a round-half element by one quantization
    bucket, so the per-step comparison allows exactly that — and the EF
    identity plus the accumulated applied stream must both hold tightly
    (quantizer unbiasedness is about the running sum, not one step)."""
    rng = np.random.default_rng(29)
    tree = {"w": jnp.asarray(rng.standard_normal((257,)), jnp.float32)}
    ef_a, ef_b = _zeros_ef(tree), _zeros_ef(tree)
    acc_a = acc_b = np.zeros(257, np.float32)
    for _ in range(10):
        deq_a, ef_a = compress_grads_int8_ef(tree, ef_a)
        g32 = np.asarray(tree["w"]) + np.asarray(ef_b["w"])  # pre-update EF
        data, ef_b = pack_grads_fused(tree, ef_b, mode="xla")
        deq_b = unpack_grads_fused(data, tree)
        scale = float(np.max(np.abs(g32))) / 127
        diff = np.abs(np.asarray(deq_b["w"]) - np.asarray(deq_a["w"]))
        assert float(np.max(diff)) <= 1.5 * scale  # at most one bucket apart
        assert int(np.count_nonzero(diff > 1e-6)) <= 3  # and only knife-edges
        # the fused EF identity: deq + new_ef == g + old_ef (to float slop)
        np.testing.assert_allclose(
            np.asarray(deq_b["w"]) + np.asarray(ef_b["w"]), g32, atol=1e-5
        )
        acc_a = acc_a + np.asarray(deq_a["w"])
        acc_b = acc_b + np.asarray(deq_b["w"])
    # both streams applied the same total update (EF carries the residual)
    np.testing.assert_allclose(acc_b / 10, acc_a / 10, atol=2e-2)


def test_fused_pack_edge_trees():
    # empty tree
    data, ef = pack_grads_fused({}, {})
    kind, specs, _ = wire.parse_grad_header(data)
    assert kind == wire.KIND_Q8 and specs == []
    assert unpack_grads_fused(data, {}) == {}
    # single scalar leaf
    t = {"s": jnp.asarray(0.75, jnp.float32)}
    want, _ = pack_grads_q8(t, _zeros_ef(t))
    for mode in ("xla", "pallas-interpret"):
        got, ef2 = pack_grads_fused(t, _zeros_ef(t), mode=mode)
        assert got == want
        assert np.shape(np.asarray(ef2["s"])) == ()
    back = unpack_grads_fused(want, t)
    assert abs(float(back["s"]) - 0.75) < 0.01
    # empty leaf next to a real one
    t2 = {"e": jnp.zeros((0,), jnp.float32), "w": jnp.ones((3,), jnp.float32)}
    want2, _ = pack_grads_q8(t2, _zeros_ef(t2))
    got2, _ = pack_grads_fused(t2, _zeros_ef(t2), mode="xla")
    assert got2 == want2
    back2 = unpack_grads_fused(want2, t2)
    assert np.asarray(back2["e"]).shape == (0,)
    np.testing.assert_allclose(np.asarray(back2["w"]), np.ones(3), atol=0.01)


def test_kernel_division_is_ieee_division():
    """The kernel divides with ``_div_rn`` because Mosaic lowers f32 ``a / b``
    to a reciprocal multiply; ``_div_rn`` must equal numpy's IEEE division
    bit for bit, at the half-integer knife edges of the quantizer too."""
    from repro.kernels.grad_pack import _div_rn

    rng = np.random.default_rng(0)
    n = 1 << 16
    b = np.exp2(rng.uniform(-46, 40, 4 * n)).astype(np.float32)
    wide = (rng.standard_normal(n) * np.exp2(rng.integers(-60, 60, n))).astype(np.float32)
    half = ((rng.integers(-127, 127, n) + 0.5) * b[n : 2 * n]).astype(np.float32)
    a = np.concatenate([wide, half, np.nextafter(half, np.inf), np.nextafter(half, -np.inf)])
    a[:6] = [0.0, -0.0, 1e-40, -1e-40, 127.0, -3.0]
    want = a / b
    got = np.asarray(jax.jit(_div_rn)(a, b))
    tiny = np.float32(2.0**-126)
    normal = (np.abs(want) >= tiny) & (np.abs(a) >= tiny)
    np.testing.assert_array_equal(got[normal], want[normal])
    assert not got[~normal].any()  # subnormal in or out: flushed to zero


def test_unpack_grads_reads_fused_wire():
    """The host unpacker and the fused unpacker agree on KIND_Q8 bytes —
    one wire format, two consumers."""
    tree, ef = _tree_for_size(2048, seed=7)
    data, _ = pack_grads_fused(tree, ef, mode="xla")
    a = unpack_grads(data, tree)
    b = unpack_grads_fused(data, tree)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_wire_is_4x_smaller_than_raw_f32():
    tree, ef = _tree_for_size(65536, seed=1)
    raw = pack_grads(tree)
    q8, _ = pack_grads_fused(tree, ef, mode="xla")
    assert len(q8) * 3.5 < len(raw)


def test_make_packer_knob_dispatch_and_parity():
    """TrainConfig.grad_pack resolves through make_packer; both packers
    emit identical wire bytes, so the knob is pure performance."""
    from repro.train.grad_sync import make_packer
    from repro.train.step import TrainConfig

    tree, ef = _tree_for_size(1024, seed=5)
    host_data, _ = make_packer(TrainConfig(grad_pack="host").grad_pack)(tree, ef)
    dev_data, _ = make_packer(TrainConfig(grad_pack="device").grad_pack)(tree, ef)
    assert host_data == dev_data
    with pytest.raises(ValueError):
        make_packer("nope")
    with pytest.raises(AssertionError):
        TrainConfig(grad_pack="nope")


# ------------------------------------------------------------ DP end-to-end


def test_dp_exchange_fused_over_comm_channel():
    """Two DP ranks exchange fused-packed gradients through a CommChannel
    and average — identical to the direct in-memory average of the
    dequantized trees (the fused analogue of the ISSUE-5 handoff test)."""
    from repro.core.comm.collective import CommChannel

    rng = np.random.default_rng(31)
    grads = [
        {"w": (jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
               jnp.asarray(rng.standard_normal((8,)), jnp.float32))}
        for _ in range(2)
    ]
    wires, deq = [], []
    for g in grads:
        data, _ = pack_grads_fused(g, _zeros_ef(g), mode="xla")
        wires.append(data)
        deq.append(unpack_grads_fused(data, g))
    channel = CommChannel()
    channel.send_request(wires[0])
    channel.send_response(wires[1])
    for _ in range(4):
        channel.progress()

    def reap_recv(source):
        for _ in range(8):
            rec = channel.reap(source)
            if rec is not None and rec.op == "recv":
                return rec
        raise AssertionError(f"no arrived payload on {source}")

    from_peer0 = unpack_grads_fused(reap_recv("request").data, grads[1])
    from_peer1 = unpack_grads_fused(reap_recv("response").data, grads[0])
    avg_comm = jax.tree.map(lambda a, b: (a + b) / 2, deq[0], from_peer1)
    avg_direct = jax.tree.map(lambda a, b: (a + b) / 2, deq[0], deq[1])
    for got, want in zip(jax.tree.leaves(avg_comm), jax.tree.leaves(avg_direct)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    avg_peer = jax.tree.map(lambda a, b: (a + b) / 2, from_peer0, deq[1])
    for got, want in zip(jax.tree.leaves(avg_peer), jax.tree.leaves(avg_direct)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- staged aggregation


def test_jax_stage_batches_one_transfer_per_drain():
    """stage='jax': a whole progress drain rides ONE staged device buffer
    — FabricStats counts one batch for N messages, and every payload
    arrives intact."""
    from repro.core.comm.collective import CommChannel

    channel = CommChannel(stage="jax")
    payloads = [bytes([i]) * (50 + i) for i in range(5)]
    for p in payloads:
        channel.send_request(p)
    channel.progress()
    st = channel.group.stats
    assert st.staged_batches == 1
    assert st.staged_bytes == sum(len(p) for p in payloads)
    got = []
    for _ in range(16):
        rec = channel.reap("request")
        if rec is not None and rec.op == "recv":
            got.append(bytes(rec.data))
    assert got == payloads


def test_jax_stage_empty_drain_counts_nothing():
    from repro.core.comm.collective import CollectiveGroup

    g = CollectiveGroup(2, 1, stage="jax")
    assert g._stage_batch([]) == []
    assert g.stats.staged_batches == 0 and g.stats.staged_bytes == 0


# ----------------------------------------------------------- copy discipline


def test_pack_grads_copy_discipline():
    """Satellite 2: contiguous host leaves go to the wire as views — the
    only big allocation in pack_grads is the joined output buffer (< 1.5x
    payload; the old np.asarray-per-leaf path allocated > 2x)."""
    leaves = [np.random.default_rng(i).standard_normal(32768).astype(np.float32)
              for i in range(4)]
    tree = {f"l{i}": a for i, a in enumerate(leaves)}
    payload = sum(a.nbytes for a in leaves)
    pack_grads(tree)  # warm any lazy imports
    tracemalloc.start()
    data = pack_grads(tree)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert len(data) > payload
    assert peak < 1.5 * payload, f"pack_grads copied leaves: peak={peak}"


def test_split_aggregate_zero_copy():
    """comm/base.py split_aggregate slices the aggregation buffer as
    memoryviews — no bytes() copy of the chunk payloads."""
    from repro.core.comm.base import aggregate_parcels, split_aggregate
    from repro.core.parcel import Chunk, Parcel

    chunks = [bytes([i]) * 20000 for i in range(6)]
    parcel = aggregate_parcels(
        [Parcel(parcel_id=i, source=0, dest=1, nzc_chunk=Chunk(c))
         for i, c in enumerate(chunks)]
    )
    total = sum(len(c) for c in chunks)
    tracemalloc.start()
    out = split_aggregate(parcel)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert [bytes(c.nzc_chunk.data) for c in out] == chunks
    assert peak < 0.5 * total, f"split_aggregate copied payloads: peak={peak}"
