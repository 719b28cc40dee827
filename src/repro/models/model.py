"""Generic LM assembly for all assigned architecture families.

One functional namespace serves every family (dense / MLA / MoE / SSM /
hybrid / VLM / enc-dec):

* ``init_params(rng, cfg)``           — stacked per-layer params (scan-ready)
* ``forward_train(params, cfg, batch)`` → (logits, aux_loss)
* ``init_cache(cfg, batch, context)``  — decode cache pytree
* ``prefill(params, cfg, batch, cache)`` → (last-token logits, cache)
* ``decode_step(params, cfg, tokens, positions, cache)`` → (logits, cache)

Every init is traceable: the dry-run builds abstract params with
``jax.eval_shape`` and never allocates.  Homogeneous layer stacks run under
``jax.lax.scan`` to keep compiled HLO size O(1) in depth; heterogeneous
behaviour inside the stack (llama4 global-attention layers, zamba2 shared
block) is expressed with ``lax.cond`` on the layer index.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..sharding.logical import shard
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (
    Params,
    cross_entropy_loss,
    dense_init,
    embed_init,
    ffn_apply,
    ffn_init,
    rms_norm,
)

__all__ = [
    "init_params",
    "forward_train",
    "loss_fn",
    "init_cache",
    "prefill",
    "decode_step",
    "model_dtype",
]


def model_dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _is_global_layer(cfg: ArchConfig, idx) -> Any:
    """llama4-style: every ``global_every``-th layer attends globally."""
    if cfg.attn_kind != "chunked" or not cfg.global_every:
        return jnp.asarray(False)
    return (idx + 1) % cfg.global_every == 0


# ------------------------------------------------------------------- params
def _layer_init(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(rng, 4)
    d = cfg.d_model
    p: Params = {"ln1": jnp.ones((d,), dtype)}
    fam = cfg.family
    if fam == "ssm" or fam == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(ks[0], cfg, dtype)
        return p
    p["attn"] = (
        attn.mla_init(ks[0], cfg, dtype) if cfg.attn_kind == "mla" else attn.attn_init(ks[0], cfg, dtype)
    )
    p["ln2"] = jnp.ones((d,), dtype)
    if cfg.is_moe:
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    else:
        p["ffn"] = ffn_init(ks[1], d, cfg.d_ff, dtype, gated=cfg.gated_ffn)
    return p


def _encoder_layer_init(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(rng, 2)
    d = cfg.d_model
    return {
        "ln1": jnp.ones((d,), dtype),
        "attn": attn.attn_init(ks[0], cfg, dtype),
        "ln2": jnp.ones((d,), dtype),
        "ffn": ffn_init(ks[1], d, cfg.d_ff, dtype, gated=cfg.gated_ffn),
    }


def _cross_layer_init(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    return {"ln": jnp.ones((d,), dtype), "attn": attn.attn_init(rng, cfg, dtype, cross=True)}


def init_params(rng: jax.Array, cfg: ArchConfig) -> Params:
    dtype = model_dtype(cfg)
    ks = jax.random.split(rng, 8)
    p: Params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (cfg.vocab_size, cfg.d_model), dtype, scale=1.0 / math.sqrt(cfg.d_model))
    layer_rngs = jax.random.split(ks[2], cfg.n_layers)
    p["layers"] = jax.vmap(lambda r: _layer_init(r, cfg, dtype))(layer_rngs)
    if cfg.family == "hybrid" and cfg.attn_every:
        p["shared_block"] = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.attn_init(ks[3], cfg, dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "ffn": ffn_init(ks[4], cfg.d_model, cfg.d_ff, dtype, gated=cfg.gated_ffn),
        }
    if cfg.is_encdec:
        enc_rngs = jax.random.split(ks[5], cfg.encoder_layers)
        p["encoder"] = jax.vmap(lambda r: _encoder_layer_init(r, cfg, dtype))(enc_rngs)
        p["enc_ln_f"] = jnp.ones((cfg.d_model,), dtype)
        cross_rngs = jax.random.split(ks[6], cfg.n_layers)
        p["cross"] = jax.vmap(lambda r: _cross_layer_init(r, cfg, dtype))(cross_rngs)
    return p


# ------------------------------------------------------------------ encoder
def _encode(params: Params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over (stubbed-frontend) frame embeddings."""
    x = shard(frames, "batch", "seq", "embed")

    def body(h, lp):
        a = attn.attention_train(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), cfg, "bidir", rope=False)
        h = h + a
        f = ffn_apply(lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps), gated=cfg.gated_ffn)
        return h + f, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


# ------------------------------------------------------------ decoder blocks
def _mixer_train(lp: Params, h: jax.Array, cfg: ArchConfig, idx) -> jax.Array:
    """Sequence mixer (attention or SSD) on a normalized input, train path."""
    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        out, _ = ssm_mod.ssm_apply(lp["ssm"], h, cfg)
        return out
    if cfg.attn_kind == "mla":
        return attn.mla_train(lp["attn"], h, cfg)
    if cfg.attn_kind == "chunked" and cfg.global_every:
        return jax.lax.cond(
            _is_global_layer(cfg, idx),
            lambda q: attn.attention_train(lp["attn"], q, cfg, "full"),
            lambda q: attn.attention_train(lp["attn"], q, cfg, "chunked", cfg.window),
            h,
        )
    return attn.attention_train(lp["attn"], h, cfg, cfg.attn_kind, cfg.window)


def _channel_train(lp: Params, h: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    if cfg.is_moe:
        return moe_mod.moe_apply(lp["moe"], h, cfg)
    return ffn_apply(lp["ffn"], h, gated=cfg.gated_ffn), jnp.asarray(0.0, jnp.float32)


def _shared_block_train(sp: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """zamba2 shared attention+MLP block (train path)."""
    a = attn.attention_train(sp["attn"], rms_norm(x, sp["ln1"], cfg.norm_eps), cfg, cfg.attn_kind, cfg.window)
    x = x + a
    f = ffn_apply(sp["ffn"], rms_norm(x, sp["ln2"], cfg.norm_eps), gated=cfg.gated_ffn)
    return x + f


REMAT_POLICIES = {
    "none": None,
    "full": "full",
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _decoder_train(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    enc_out: Optional[jax.Array],
    remat: str = "none",
) -> Tuple[jax.Array, jax.Array]:
    """Scan the decoder stack; returns (hidden, aux_loss_sum)."""
    idxs = jnp.arange(cfg.n_layers)
    shared = params.get("shared_block")
    cross = params.get("cross")

    def body(carry, inp):
        h, aux = carry
        lp, idx = inp[0], inp[1]
        a = _mixer_train(lp, rms_norm(h, lp["ln1"], cfg.norm_eps), cfg, idx)
        h = h + a
        h = shard(h, "batch", "seq", "embed")
        if cross is not None:
            cp = inp[2]
            ca = attn.attention_train(cp["attn"], rms_norm(h, cp["ln"], cfg.norm_eps), cfg, "bidir", kv_x=enc_out, rope=False)
            h = h + ca
        if "ln2" in lp:
            f, a_loss = _channel_train(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
            h = h + f
            aux = aux + a_loss
        if shared is not None:
            h = jax.lax.cond(
                idx % cfg.attn_every == 0,
                lambda q: _shared_block_train(shared, q, cfg),
                lambda q: q,
                h,
            )
        h = shard(h, "batch", "seq", "embed")
        return (h, aux), None

    xs = (params["layers"], idxs) if cross is None else (params["layers"], idxs, cross)
    fn = body
    if remat != "none":
        policy = REMAT_POLICIES[remat]
        fn = jax.checkpoint(body, policy=None if policy == "full" else policy)
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.asarray(0.0, jnp.float32)), xs)
    return x, aux


def _embed_tokens(params: Params, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]  # gather; vocab sharded → all-gather of slices
    return shard(x, "batch", "seq", "embed")


def _logits(params: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head)
    return shard(logits, "batch", "seq", "vocab")


def forward_train(
    params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], remat: str = "none"
) -> Tuple[jax.Array, jax.Array]:
    """batch: tokens (B,S_text) [+ 'prefix' (B,P,D) | 'frames' (B,F,D)]."""
    x = _embed_tokens(params, cfg, batch["tokens"])
    if cfg.frontend == "vision" and "prefix" in batch:
        x = jnp.concatenate([batch["prefix"].astype(x.dtype), x], axis=1)
        x = shard(x, "batch", "seq", "embed")
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, batch["frames"])
    x, aux = _decoder_train(params, cfg, x, enc_out, remat=remat)
    return _logits(params, cfg, x), aux


def loss_fn(
    params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], remat: str = "none"
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward_train(params, cfg, batch, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision" and "prefix" in batch:
        # loss only over text positions (prefix contributes context)
        logits = logits[:, batch["prefix"].shape[1] :]
    mask = (labels >= 0).astype(jnp.float32)
    xent = cross_entropy_loss(logits, jnp.maximum(labels, 0), mask)
    total = xent + cfg.router_aux_coef * aux
    return total, {"loss": total, "xent": xent, "aux": aux}


# ------------------------------------------------------------------- caches
def init_cache(cfg: ArchConfig, batch: int, context: int) -> Params:
    """Stacked (per-layer leading dim) decode cache."""
    dtype = model_dtype(cfg)
    L = cfg.n_layers
    cache: Params = {}
    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        one = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        cache["ssm"] = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)
    if fam == "hybrid" and cfg.attn_every:
        n_inv = (L + cfg.attn_every - 1) // cfg.attn_every
        one = attn.init_kv_cache(cfg, batch, context, dtype)
        cache["shared_attn"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_inv,) + a.shape).copy(), one
        )
    if fam not in ("ssm", "hybrid"):
        if cfg.attn_kind == "mla":
            one = attn.init_mla_cache(cfg, batch, context, dtype)
        else:
            one = attn.init_kv_cache(cfg, batch, context, dtype)
        cache["kv"] = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)
    if cfg.is_encdec:
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cache["cross_kv"] = {
            "k": jnp.zeros((L, batch, cfg.encoder_seq, kv, hd), dtype),
            "v": jnp.zeros((L, batch, cfg.encoder_seq, kv, hd), dtype),
        }
    return cache


def _shard_cache(cache: Params) -> Params:
    def ann(path, a):
        if a.ndim == 5:  # (L,B,S,KV,hd)
            return shard(a, None, "batch", "seq_kv", "kv_heads", "head_dim")
        if a.ndim == 4:
            return shard(a, None, "batch", "seq_kv", None)
        return a

    return jax.tree_util.tree_map_with_path(ann, cache)


# ------------------------------------------------------------------ prefill
def prefill(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], cache: Params) -> Tuple[jax.Array, Params]:
    """Process the prompt; returns (logits for the last position, cache)."""
    x = _embed_tokens(params, cfg, batch["tokens"])
    if cfg.frontend == "vision" and "prefix" in batch:
        x = jnp.concatenate([batch["prefix"].astype(x.dtype), x], axis=1)
    enc_out = _encode(params, cfg, batch["frames"]) if cfg.is_encdec else None
    idxs = jnp.arange(cfg.n_layers)
    shared = params.get("shared_block")
    cross = params.get("cross")
    new_cache = dict(cache)

    if cfg.is_encdec:
        # cross K/V computed once per request
        def cross_kv(cp):
            k = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", enc_out, cp["attn"]["wv"])
            return k, v

        ck, cv = jax.vmap(cross_kv)(cross)
        new_cache["cross_kv"] = {"k": ck.astype(model_dtype(cfg)), "v": cv.astype(model_dtype(cfg))}

    def body(carry, inp):
        h = carry
        lp, idx, lc = inp[0], inp[1], inp[2]
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        out_lc = lc
        fam = cfg.family
        if fam in ("ssm", "hybrid"):
            a, new_state = ssm_mod.ssm_apply(lp["ssm"], hn, cfg, state=lc.get("ssm_slice"))
            out_lc = dict(lc)
            out_lc["ssm_slice"] = new_state
        elif cfg.attn_kind == "mla":
            a, kvc = attn.mla_prefill(lp["attn"], hn, cfg, lc["kv_slice"])
            out_lc = dict(lc)
            out_lc["kv_slice"] = kvc
        else:
            if cfg.attn_kind == "chunked" and cfg.global_every:
                a, kvc = jax.lax.cond(
                    _is_global_layer(cfg, idx),
                    lambda q, c: attn.attention_prefill(lp["attn"], q, cfg, c, "full"),
                    lambda q, c: attn.attention_prefill(lp["attn"], q, cfg, c, "chunked", cfg.window),
                    hn,
                    lc["kv_slice"],
                )
            else:
                a, kvc = attn.attention_prefill(lp["attn"], hn, cfg, lc["kv_slice"], cfg.attn_kind, cfg.window)
            out_lc = dict(lc)
            out_lc["kv_slice"] = kvc
        h = h + a
        if cross is not None:
            cp = inp[3]
            ca = attn.attention_train(cp["attn"], rms_norm(h, cp["ln"], cfg.norm_eps), cfg, "bidir", kv_x=enc_out, rope=False)
            h = h + ca
        if "ln2" in lp:
            f, _ = _channel_train(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
            h = h + f
        if shared is not None:
            inv = idx // cfg.attn_every

            def with_attn(q, sc):
                sl = jax.tree.map(lambda a: a[inv], sc)
                a2, new_sl = attn.attention_prefill(sp_attn(shared), rms_norm(q, shared["ln1"], cfg.norm_eps), cfg, sl, cfg.attn_kind, cfg.window)
                q = q + a2
                f2 = ffn_apply(shared["ffn"], rms_norm(q, shared["ln2"], cfg.norm_eps), gated=cfg.gated_ffn)
                sc = jax.tree.map(lambda full, piece: jax.lax.dynamic_update_index_in_dim(full, piece.astype(full.dtype), inv, 0), sc, new_sl)
                return q + f2, sc

            h, sa = jax.lax.cond(
                idx % cfg.attn_every == 0,
                with_attn,
                lambda q, sc: (q, sc),
                h,
                out_lc["shared_attn_all"],
            )
            out_lc = dict(out_lc)
            out_lc["shared_attn_all"] = sa
        return h, out_lc

    # assemble per-layer xs
    layer_xs: Dict[str, Any] = {}
    if "ssm" in cache:
        layer_xs["ssm_slice"] = cache["ssm"]
    if "kv" in cache:
        layer_xs["kv_slice"] = cache["kv"]
    # shared_attn is carried, not scanned — thread via carry below if present
    if "shared_attn" in cache:
        return _prefill_hybrid(params, cfg, x, cache, layer_xs)
    xs = (params["layers"], idxs, layer_xs) if cross is None else (params["layers"], idxs, layer_xs, cross)
    x, out_layer_caches = jax.lax.scan(body, x, xs)
    for k_ in ("ssm_slice", "kv_slice"):
        if k_ in out_layer_caches:
            new_cache[{"ssm_slice": "ssm", "kv_slice": "kv"}[k_]] = out_layer_caches[k_]
    logits = _logits(params, cfg, x[:, -1:, :])
    return logits, new_cache


def sp_attn(shared: Params) -> Params:
    return shared["attn"]


def _prefill_hybrid(params, cfg, x, cache, layer_xs):
    """zamba2 prefill: ssm states scanned, shared-attn cache carried."""
    idxs = jnp.arange(cfg.n_layers)
    shared = params["shared_block"]

    def body(carry, inp):
        h, sa = carry
        lp, idx, lc = inp
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        a, new_state = ssm_mod.ssm_apply(lp["ssm"], hn, cfg, state=lc["ssm_slice"])
        h = h + a
        inv = idx // cfg.attn_every

        def with_attn(q, sc):
            sl = jax.tree.map(lambda t: t[inv], sc)
            a2, new_sl = attn.attention_prefill(
                shared["attn"], rms_norm(q, shared["ln1"], cfg.norm_eps), cfg, sl, cfg.attn_kind, cfg.window
            )
            q = q + a2
            f2 = ffn_apply(shared["ffn"], rms_norm(q, shared["ln2"], cfg.norm_eps), gated=cfg.gated_ffn)
            sc = jax.tree.map(
                lambda full, piece: jax.lax.dynamic_update_index_in_dim(full, piece.astype(full.dtype), inv, 0),
                sc,
                new_sl,
            )
            return q + f2, sc

        h, sa = jax.lax.cond(idx % cfg.attn_every == 0, with_attn, lambda q, sc: (q, sc), h, sa)
        return (h, sa), {"ssm_slice": new_state}

    (x, sa), outs = jax.lax.scan(body, (x, cache["shared_attn"]), (params["layers"], idxs, layer_xs))
    new_cache = dict(cache)
    new_cache["ssm"] = outs["ssm_slice"]
    new_cache["shared_attn"] = sa
    logits = _logits(params, cfg, x[:, -1:, :])
    return logits, new_cache


# -------------------------------------------------------------- decode step
def decode_step(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,  # (B, 1)
    positions: jax.Array,  # (B,) absolute position of the new token
    cache: Params,
) -> Tuple[jax.Array, Params]:
    x = _embed_tokens(params, cfg, tokens)
    idxs = jnp.arange(cfg.n_layers)
    shared = params.get("shared_block")
    cross = params.get("cross")
    new_cache = dict(cache)

    # The stacked caches decode writes ride in the carry and are updated in
    # place at the layer's index, so the donated buffer that comes in is the
    # one that goes out. Scanned as xs -> ys, XLA copies the whole stack.
    written = {k: cache[k] for k in ("ssm", "kv") if k in cache}
    layer_xs = (params["layers"], idxs)
    if cross is not None:
        layer_xs += (cache["cross_kv"], cross)

    def body(carry, inp):
        h, sa, st = carry
        lp, idx = inp[0], inp[1]
        lc = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, idx, 0, keepdims=False), st)
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        out_lc = {}
        fam = cfg.family
        if fam in ("ssm", "hybrid"):
            a, out_lc["ssm"] = ssm_mod.ssm_decode(lp["ssm"], hn, cfg, lc["ssm"])
        elif cfg.attn_kind == "mla":
            a, out_lc["kv"] = attn.mla_decode(lp["attn"], hn, cfg, lc["kv"], positions)
        else:
            if cfg.attn_kind == "chunked" and cfg.global_every:
                a, kvc = jax.lax.cond(
                    _is_global_layer(cfg, idx),
                    lambda q, c: attn.attention_decode(lp["attn"], q, cfg, c, positions, "full"),
                    lambda q, c: attn.attention_decode(lp["attn"], q, cfg, c, positions, "chunked", cfg.window),
                    hn,
                    lc["kv"],
                )
            else:
                a, kvc = attn.attention_decode(lp["attn"], hn, cfg, lc["kv"], positions, cfg.attn_kind, cfg.window)
            out_lc["kv"] = kvc
        h = h + a
        if cross is not None:
            cross_slice, cp = inp[2], inp[3]
            ck, cv_ = cross_slice["k"], cross_slice["v"]
            hq = rms_norm(h, cp["ln"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", hq, cp["attn"]["wq"])
            g = cfg.n_heads // cfg.n_kv_heads
            b = q.shape[0]
            qg = q.reshape(b, 1, cfg.n_kv_heads, g, cfg.resolved_head_dim)
            sc = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck).astype(jnp.float32) / math.sqrt(cfg.resolved_head_dim)
            pr = jax.nn.softmax(sc, axis=-1).astype(cv_.dtype)
            ca = jnp.einsum("bkgqs,bskh->bqkgh", pr, cv_).reshape(b, 1, cfg.n_heads, cfg.resolved_head_dim)
            h = h + jnp.einsum("bshk,hkd->bsd", ca, cp["attn"]["wo"])
        if "ln2" in lp:
            f, _ = _channel_train(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
            h = h + f
        if shared is not None:
            inv = idx // cfg.attn_every

            def with_attn(q, sc2):
                sl = jax.tree.map(lambda t: t[inv], sc2)
                a2, new_sl = attn.attention_decode(
                    shared["attn"], rms_norm(q, shared["ln1"], cfg.norm_eps), cfg, sl, positions, cfg.attn_kind, cfg.window
                )
                q = q + a2
                f2 = ffn_apply(shared["ffn"], rms_norm(q, shared["ln2"], cfg.norm_eps), gated=cfg.gated_ffn)
                sc2 = jax.tree.map(
                    lambda full, piece: jax.lax.dynamic_update_index_in_dim(full, piece.astype(full.dtype), inv, 0),
                    sc2,
                    new_sl,
                )
                return q + f2, sc2

            h, sa = jax.lax.cond(idx % cfg.attn_every == 0, with_attn, lambda q, s: (q, s), h, sa)
        st = jax.tree.map(lambda full, piece: jax.lax.dynamic_update_index_in_dim(full, piece, idx, 0), st, out_lc)
        return (h, sa, st), None

    sa0 = cache.get("shared_attn", jnp.zeros((1,), jnp.int32))
    (x, sa, written), _ = jax.lax.scan(body, (x, sa0, written), layer_xs)
    new_cache.update(written)
    if "shared_attn" in cache:
        new_cache["shared_attn"] = sa
    logits = _logits(params, cfg, x)
    return logits, new_cache
