"""Operations and bytes that a kernel or program needs, from its shapes.

The counts are of the algorithm, not of how a kernel computes it: the SSD
chunk step's prefix sums, which the kernel does as matmuls against a mask,
count nothing; bytes are each input read once and each output written
once.  A roofline share is then ``max(flops / peak_flops, bytes / peak_bw) / device_time``, which a
correct count and a full time can never push past 100%.
"""
from __future__ import annotations

from typing import Dict

__all__ = ["ssd_chunk", "forward_matmul_params", "prefill_flops", "decode_live_bytes"]


def ssd_chunk(b: int, h: int, g: int, s: int, q: int, p: int, n: int, in_bytes: int = 2) -> Dict[str, float]:
    """``ssd_chunk_kernel`` over ``b`` sequences of ``s`` (a multiple of
    ``q``) positions: per (sequence, head, chunk) the causal block
    y = (C Bᵀ ∘ L) X and the chunk state Xᵀ (B ∘ decay)."""
    nc = s // q
    pairs = q * (q + 1) / 2  # causal (i, j) pairs of one chunk
    flops = b * h * nc * (2 * pairs * n + 2 * pairs * p + 2 * q * p * n)
    read = b * h * s * 4 + b * h * s * p * in_bytes + 2 * b * g * s * n * in_bytes
    write = b * h * s * p * in_bytes + b * h * nc * p * n * 4
    return {"flops": flops, "bytes": read + write}


def forward_matmul_params(arch: Dict) -> float:
    """Weight elements one token meets in a forward pass, each use of a
    shared block counted, the embedding gather and the output head not."""
    d = arch["d_model"]
    per = 0.0
    if arch.get("ssm_state"):
        d_in = arch["ssm_expand"] * d
        heads = d_in // arch["ssm_head_dim"]
        per += arch["n_layers"] * (d * (2 * d_in + 2 * arch["ssm_state"] + heads) + d_in * d)
    if arch.get("attn_every"):
        hd = d // arch["n_heads"]
        attn = d * hd * (2 * arch["n_heads"] + 2 * arch["n_kv_heads"])
        ffn = (3 if arch.get("gated_ffn", True) else 2) * d * arch["d_ff"]
        uses = -(-arch["n_layers"] // arch["attn_every"])
        per += uses * (attn + ffn)
    return per


def prefill_flops(arch: Dict, prompt_tokens: float, prefills: float) -> float:
    """2 · N · prompt tokens, plus one row of the output head per prefill
    (the program computes logits for the last position only)."""
    return 2 * forward_matmul_params(arch) * prompt_tokens + 2 * arch["d_model"] * arch["vocab_size"] * prefills


def decode_live_bytes(arch: Dict, live_positions: float, slots: int, weight_bytes: float, elt: int = 2) -> float:
    """Least HBM traffic of one batched decode step: the weights once, the
    SSM and conv state of every slot read and written, and the keys and
    values of the live positions only, read, plus one new row written.

    ``repro.roofline.analysis.decode_min_bytes`` counts the whole ring
    (``context``) as live; this counts ``live_positions`` (the sum over
    slots of their current lengths), so a kernel that stops at the live
    length can show as nearer its floor."""
    d = arch["d_model"]
    total = weight_bytes
    if arch.get("ssm_state"):
        d_in = arch["ssm_expand"] * d
        heads = d_in // arch["ssm_head_dim"]
        state = heads * arch["ssm_head_dim"] * arch["ssm_state"] + (arch["ssm_conv"] - 1) * (d_in + 2 * arch["ssm_state"])
        total += 2 * arch["n_layers"] * slots * state * elt
    if arch.get("attn_every"):
        uses = -(-arch["n_layers"] // arch["attn_every"])
        kv_row = 2 * arch["n_kv_heads"] * (d // arch["n_heads"]) * elt
        total += uses * (live_positions + slots) * kv_row
    return total
