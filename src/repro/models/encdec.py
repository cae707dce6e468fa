"""Whisper-style encoder-decoder backbone.

The conv/audio frontend is a STUB per the assignment: the model consumes
precomputed frame embeddings (B, n_audio_frames, d_model) from input_specs().
Encoder: bidirectional self-attn; decoder: causal self-attn + cross-attn.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.attention import (gqa_decode, gqa_forward, gqa_params,
                                    init_gqa_cache)
from repro.models.common import (apply_mlp, apply_norm, cross_entropy,
                                 embed_tokens, mlp_params, norm_params,
                                 sinusoidal_positions)
from repro.models.sharding import shard
from repro.models.transformer import REMAT_POLICIES, _maybe_remat


def init_encdec(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 6)

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": norm_params(cfg, dtype), "ln2": norm_params(cfg, dtype),
                "attn": gqa_params(k1, cfg, dtype), "mlp": mlp_params(k2, cfg, dtype=dtype)}

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": norm_params(cfg, dtype), "ln2": norm_params(cfg, dtype),
                "ln3": norm_params(cfg, dtype),
                "self_attn": gqa_params(k1, cfg, dtype),
                "cross_attn": gqa_params(k2, cfg, dtype, cross=True),
                "mlp": mlp_params(k3, cfg, dtype=dtype)}

    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype),
        "enc_layers": jax.vmap(enc_layer)(jax.random.split(ks[1], cfg.n_encoder_layers)),
        "enc_ln_f": norm_params(cfg, dtype),
        "dec_layers": jax.vmap(dec_layer)(jax.random.split(ks[2], cfg.n_layers)),
        "dec_ln_f": norm_params(cfg, dtype),
    }


def encode(params, cfg, audio_embed, *, impl="chunked", chunk=1024, remat="none"):
    S = audio_embed.shape[1]
    h = audio_embed + sinusoidal_positions(S, cfg.d_model, audio_embed.dtype)[None]
    h = shard(h, "batch", "seq", None)

    def block(lp, hh):
        a = gqa_forward(lp["attn"], apply_norm(lp["ln1"], hh, cfg.norm), cfg,
                        causal=False, impl=impl, chunk=chunk)
        hh = hh + a
        m = apply_mlp(lp["mlp"], apply_norm(lp["ln2"], hh, cfg.norm), cfg.activation)
        return shard(hh + m, "batch", "seq", None)

    block = _maybe_remat(block, remat)

    def body(carry, lp):
        return block(lp, carry), None

    h, _ = jax.lax.scan(body, h, params["enc_layers"])
    return apply_norm(params["enc_ln_f"], h, cfg.norm)


def _dec_block(lp, hh, enc_h, cfg, impl, chunk, return_kv=False):
    a = gqa_forward(lp["self_attn"], apply_norm(lp["ln1"], hh, cfg.norm), cfg,
                    causal=True, impl=impl, chunk=chunk, return_kv=return_kv)
    if return_kv:
        a, self_kv = a
    hh = hh + a
    c = gqa_forward(lp["cross_attn"], apply_norm(lp["ln2"], hh, cfg.norm), cfg,
                    kv_x=enc_h, causal=False, impl=impl, chunk=chunk, return_kv=return_kv)
    if return_kv:
        c, cross_kv = c
    hh = hh + c
    m = apply_mlp(lp["mlp"], apply_norm(lp["ln3"], hh, cfg.norm), cfg.activation)
    hh = shard(hh + m, "batch", "seq", None)
    if return_kv:
        return hh, {"self": self_kv, "cross": cross_kv}
    return hh


def forward_encdec(params, cfg, tokens, audio_embed, *, impl="chunked", chunk=1024,
                   remat="none", return_cache=False):
    enc_h = encode(params, cfg, audio_embed, impl=impl, chunk=chunk, remat=remat)
    B, S = tokens.shape
    h = embed_tokens(params["embed"], tokens)
    h = h + sinusoidal_positions(S, cfg.d_model, h.dtype)[None]
    block = _maybe_remat(functools.partial(
        _dec_block, enc_h=enc_h, cfg=cfg, impl=impl, chunk=chunk,
        return_kv=return_cache), remat)

    def body(carry, lp):
        if return_cache:
            h2, kv = block(lp, carry)
            return h2, kv
        return block(lp, carry), None

    h, kvs = jax.lax.scan(body, h, params["dec_layers"])
    h = apply_norm(params["dec_ln_f"], h, cfg.norm)
    if return_cache:
        h = h[:, -1:]  # prefill: last-position logits only
    w = shard(params["embed"], "tp", None).T  # vocab-sharded head (see transformer._logits)
    logits = h @ w.astype(h.dtype)
    logits = shard(logits, "batch", "seq", "tp")
    if return_cache:
        return logits, kvs
    return logits


def loss_encdec(params, cfg, batch, *, impl="chunked", chunk=1024, remat="none"):
    tokens = batch["tokens"]
    logits = forward_encdec(params, cfg, tokens, batch["audio_embed"],
                            impl=impl, chunk=chunk, remat=remat)
    return cross_entropy(logits[:, :-1], tokens[:, 1:])


def init_cache_encdec(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    L = cfg.n_layers
    self_c = init_gqa_cache(cfg, batch, max_len, dtype)
    cross_shape = (L, batch, cfg.n_kv_heads, cfg.n_audio_frames, cfg.head_dim)
    return {
        "self": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), self_c),
        "cross": {"k": jnp.zeros(cross_shape, dtype), "v": jnp.zeros(cross_shape, dtype)},
    }


def decode_step_encdec(params, cfg, cache, tokens, cache_len):
    """One-token decoder step against a prepared cross-KV cache."""
    h = embed_tokens(params["embed"], tokens)
    pos_table = sinusoidal_positions(cache["self"]["k"].shape[-2], cfg.d_model, h.dtype)
    h = h + jax.lax.dynamic_slice_in_dim(pos_table, cache_len, 1, 0)[None]

    def body(carry, xs):
        hh = carry
        lp, sc, cc = xs
        x = apply_norm(lp["ln1"], hh, cfg.norm)
        a, sc_new = gqa_decode(lp["self_attn"], x, sc, cache_len, cfg)
        hh = hh + a
        x = apply_norm(lp["ln2"], hh, cfg.norm)
        c, _ = gqa_decode(lp["cross_attn"], x, None, cache_len, cfg,
                          cross_kv=(cc["k"], cc["v"]))
        hh = hh + c
        m = apply_mlp(lp["mlp"], apply_norm(lp["ln3"], hh, cfg.norm), cfg.activation)
        return hh + m, sc_new

    h, new_self = jax.lax.scan(body, h, (params["dec_layers"], cache["self"], cache["cross"]))
    h = apply_norm(params["dec_ln_f"], h, cfg.norm)
    w = shard(params["embed"], "tp", None).T
    logits = h @ w.astype(h.dtype)
    return logits, {"self": new_self, "cross": cache["cross"]}
