"""decode_step updates the donated stacked cache in place.

The per-layer caches that decode writes (``ssm``, ``kv``) ride in the layer
loop's carry and are written back at the layer's index.  Two things are
checked: the arithmetic is the old loop's, bit for bit, for every family;
and the compiled program holds no copy of a whole written stack.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _cache_copies import whole_stack_copies
from repro.configs import SMOKES
from repro.models import attention as attn
from repro.models import decode_step, init_cache, init_params, prefill
from repro.models import ssm as ssm_mod
from repro.models.layers import ffn_apply, rms_norm
from repro.models.model import _channel_train, _embed_tokens, _is_global_layer, _logits


def _decode_step_xs_ys(params, cfg, tokens, positions, cache):
    """The layer loop as it was: each written cache scanned in as ``xs`` and
    stacked back out as ``ys``."""
    x = _embed_tokens(params, cfg, tokens)
    idxs = jnp.arange(cfg.n_layers)
    shared = params.get("shared_block")
    cross = params.get("cross")
    new_cache = dict(cache)
    layer_xs = {}
    if "ssm" in cache:
        layer_xs["ssm_slice"] = cache["ssm"]
    if "kv" in cache:
        layer_xs["kv_slice"] = cache["kv"]
    if "cross_kv" in cache:
        layer_xs["cross_slice"] = cache["cross_kv"]

    def body(carry, inp):
        h, sa = carry
        lp, idx, lc = inp[0], inp[1], inp[2]
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        out_lc = dict(lc)
        if cfg.family in ("ssm", "hybrid"):
            a, out_lc["ssm_slice"] = ssm_mod.ssm_decode(lp["ssm"], hn, cfg, lc["ssm_slice"])
        elif cfg.attn_kind == "mla":
            a, out_lc["kv_slice"] = attn.mla_decode(lp["attn"], hn, cfg, lc["kv_slice"], positions)
        elif cfg.attn_kind == "chunked" and cfg.global_every:
            a, out_lc["kv_slice"] = jax.lax.cond(
                _is_global_layer(cfg, idx),
                lambda q, c: attn.attention_decode(lp["attn"], q, cfg, c, positions, "full"),
                lambda q, c: attn.attention_decode(lp["attn"], q, cfg, c, positions, "chunked", cfg.window),
                hn,
                lc["kv_slice"],
            )
        else:
            a, out_lc["kv_slice"] = attn.attention_decode(
                lp["attn"], hn, cfg, lc["kv_slice"], positions, cfg.attn_kind, cfg.window
            )
        h = h + a
        if cross is not None:
            cp = inp[3]
            ck, cv_ = lc["cross_slice"]["k"], lc["cross_slice"]["v"]
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
        return (h, sa), out_lc

    sa0 = cache.get("shared_attn", jnp.zeros((1,), jnp.int32))
    xs = (params["layers"], idxs, layer_xs) if cross is None else (params["layers"], idxs, layer_xs, cross)
    (x, sa), out_layer = jax.lax.scan(body, (x, sa0), xs)
    for src, dst in (("ssm_slice", "ssm"), ("kv_slice", "kv")):
        if src in out_layer:
            new_cache[dst] = out_layer[src]
    if "shared_attn" in cache:
        new_cache["shared_attn"] = sa
    return _logits(params, cfg, x), new_cache


def _prefilled(cfg, batch, context):
    """Params, and a cache that a prompt of 5 tokens has filled; the slots
    then decode at different positions."""
    rng = jax.random.PRNGKey(7)
    params = init_params(rng, cfg)
    n_pre = cfg.n_prefix_tokens if cfg.frontend == "vision" else 0
    prompt = {"tokens": jax.random.randint(rng, (batch, 5), 0, cfg.vocab_size)}
    if cfg.frontend == "vision":
        prompt["prefix"] = jax.random.normal(rng, (batch, n_pre, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        prompt["frames"] = jax.random.normal(rng, (batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    _, cache = prefill(params, cfg, prompt, init_cache(cfg, batch, context))
    positions = 5 + n_pre + 2 * jnp.arange(batch, dtype=jnp.int32)
    return params, cache, positions


@pytest.mark.parametrize("name", list(SMOKES))
def test_decode_step_bit_identical_to_xs_ys_loop(name):
    """Logits and every cache leaf equal the old loop's, bit for bit, over
    four steps of three slots at different positions."""
    cfg = SMOKES[name]
    params, cache, positions = _prefilled(cfg, 3, 32)
    new = jax.jit(lambda p, t, pos, c: decode_step(p, cfg, t, pos, c))
    old = jax.jit(lambda p, t, pos, c: _decode_step_xs_ys(p, cfg, t, pos, c))
    c_new = c_old = cache
    tok = jnp.asarray([[1], [2], [3]], jnp.int32)
    for _ in range(4):
        l_new, c_new = new(params, tok, positions, c_new)
        l_old, c_old = old(params, tok, positions, c_old)
        np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_old))
        assert jax.tree.structure(c_new) == jax.tree.structure(c_old)
        for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_old)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tok = jnp.argmax(l_new[:, 0], axis=-1)[:, None].astype(jnp.int32)
        positions = positions + 1


def test_decode_step_holds_no_whole_state_copy_on_cpu():
    """The compiled decode program, cache donated, copies no whole stacked
    SSM state.  The config is float32: the CPU backend widens bf16
    arithmetic to f32, and that widening brings copies of its own into the
    loop body.  The attention families are checked on a described TPU in
    ``test_tpu_compile.py``: the CPU backend copies the carried stack in the
    loop body whenever the written slice has a reader besides the write,
    which attention's score and value products are."""
    cfg = SMOKES["mamba2-130m"].variant(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 4, 32)
    fn = jax.jit(lambda p, t, pos, c: decode_step(p, cfg, t, pos, c), donate_argnums=(3,))
    hlo = fn.lower(params, jnp.zeros((4, 1), jnp.int32), jnp.zeros((4,), jnp.int32), cache).compile().as_text()
    assert whole_stack_copies(hlo, cache) == []
