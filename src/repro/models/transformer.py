"""Decoder-only LM assembly for dense / MoE / VLM / SSM / hybrid families.

Layers are stacked along a leading axis and driven by ``lax.scan`` so the HLO
(and compile time) is independent of depth; non-uniform structure (first-k
dense MoE layers, cross-attn every Nth block, zamba's shared block) is handled
by scanning over uniform *groups*. A layer pattern (``cfg.layer_types``,
granite-4.0-h) keeps each run of consecutive layers of one kind as one stack
under ``params["runs"]`` and scans the runs in order.
"""
from __future__ import annotations

import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import ssm
from repro.models.attention import (attn_params, gqa_decode, gqa_decode_paged,
                                    gqa_decode_spec, gqa_decode_spec_paged,
                                    gqa_forward, gqa_params, gqa_prefill_paged,
                                    init_gqa_cache, init_gqa_pool,
                                    init_mla_cache, init_mla_pool, mla_decode,
                                    mla_decode_paged, mla_forward,
                                    mla_prefill_paged)
from repro.models.common import (apply_mlp, apply_norm, cross_entropy,
                                 dense_init, embed_tokens, mlp_params,
                                 norm_params)
from repro.models.moe import apply_moe, moe_params
from repro.models.sharding import shard

REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "full": jax.checkpoint_policies.nothing_saveable,
}


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    return jax.checkpoint(fn, policy=REMAT_POLICIES[remat])


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def init_dense_layer(key, cfg, dtype, moe: bool = False, d_ff: Optional[int] = None,
                     mixer: str = "attention"):
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_params(cfg, dtype), "ln2": norm_params(cfg, dtype)}
    if mixer == "mamba":
        p["mamba"] = ssm.mamba2_params(k1, cfg, dtype)
    else:
        p["attn"] = attn_params(k1, cfg, dtype)
    if moe:
        p["moe"] = moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_params(k2, cfg, d_ff=d_ff, dtype=dtype)
    return p


def init_cross_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_params(cfg, dtype), "ln2": norm_params(cfg, dtype),
        "attn": gqa_params(k1, cfg, dtype, cross=True),
        "mlp": mlp_params(k2, cfg, dtype=dtype),
        "gate_attn": jnp.zeros((), dtype),
        "gate_mlp": jnp.zeros((), dtype),
    }


def init_rwkv_layer(key, cfg, dtype):
    p = ssm.rwkv6_params(key, cfg, dtype)
    p["ln1"] = norm_params(cfg, dtype)
    p["ln2"] = norm_params(cfg, dtype)
    return p


def init_mamba_layer(key, cfg, dtype):
    return {"ln": norm_params(cfg, dtype), "mamba": ssm.mamba2_params(key, cfg, dtype)}


def init_shared_attn_block(key, cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    return {
        "w_in": dense_init(k1, 2 * d, d, dtype),
        "ln1": norm_params(cfg, dtype), "ln2": norm_params(cfg, dtype),
        "attn": gqa_params(k2, cfg, dtype),
        "mlp": mlp_params(k3, cfg, dtype=dtype),
        "w_out_proj": dense_init(k4, d, d, dtype),
    }


# ---------------------------------------------------------------------------
# block applications (train / prefill)
# ---------------------------------------------------------------------------


def _residual(h, y, cfg):
    """``h + y``, the branch scaled by Granite's residual multiplier where it
    is not neutral."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return shard(h + y, "batch", "seq", None)


def dense_block(p, h, cfg, positions, impl, chunk, return_kv=False, moe_cf=1.25):
    """Standard pre-norm block: a mixer (attention, or Mamba2 in a layer
    pattern), then the MLP or MoE. Returns (h, aux[, kv_cache_entry])."""
    x = apply_norm(p["ln1"], h, cfg.norm)
    kv = None
    if "mamba" in p:
        a = ssm.mamba2_forward(p["mamba"], x, cfg)
    elif cfg.use_mla:
        if return_kv:
            a, kv = mla_forward(p["attn"], x, cfg, positions=positions, impl=impl,
                                chunk=chunk, return_cache=True)
        else:
            a = mla_forward(p["attn"], x, cfg, positions=positions, impl=impl, chunk=chunk)
    else:
        if return_kv:
            a, kv = gqa_forward(p["attn"], x, cfg, positions=positions, impl=impl,
                                chunk=chunk, return_kv=True)
        else:
            a = gqa_forward(p["attn"], x, cfg, positions=positions, impl=impl, chunk=chunk)
    h = _residual(h, a, cfg)
    x = apply_norm(p["ln2"], h, cfg.norm)
    if "moe" in p:
        m, aux = apply_moe(p["moe"], x, cfg, capacity_factor=moe_cf)
    else:
        m, aux = apply_mlp(p["mlp"], x, cfg.activation), jnp.zeros((), jnp.float32)
    h = _residual(h, m, cfg)
    return (h, aux, kv) if return_kv else (h, aux)


def cross_block(p, h, cfg, kv_x, return_kv=False):
    """Gated cross-attention block (llama-3.2-vision style)."""
    x = apply_norm(p["ln1"], h, cfg.norm)
    if return_kv:
        a, kv = gqa_forward(p["attn"], x, cfg, kv_x=kv_x, causal=False, return_kv=True)
    else:
        a = gqa_forward(p["attn"], x, cfg, kv_x=kv_x, causal=False)
    h = h + jnp.tanh(p["gate_attn"]) * a
    m = apply_mlp(p["mlp"], apply_norm(p["ln2"], h, cfg.norm), cfg.activation)
    h = h + jnp.tanh(p["gate_mlp"]) * m
    h = shard(h, "batch", "seq", None)
    return (h, kv) if return_kv else h


def rwkv_block(p, h, cfg):
    t = ssm.rwkv6_tmix(p["tmix"], apply_norm(p["ln1"], h, cfg.norm), cfg)
    h = h + t
    c, _ = ssm.rwkv6_cmix(p["cmix"], apply_norm(p["ln2"], h, cfg.norm))
    return shard(h + c, "batch", "seq", None)


def mamba_block(p, h, cfg):
    m = ssm.mamba2_forward(p["mamba"], apply_norm(p["ln"], h, cfg.norm), cfg)
    return shard(h + m, "batch", "seq", None)


def shared_attn_apply(p, h, emb0, cfg, impl, chunk, positions, return_kv=False):
    u = jnp.concatenate([h, emb0], axis=-1) @ p["w_in"]
    x = apply_norm(p["ln1"], u, cfg.norm)
    if return_kv:
        a, kv = gqa_forward(p["attn"], x, cfg, positions=positions, impl=impl,
                            chunk=chunk, return_kv=True)
    else:
        a = gqa_forward(p["attn"], x, cfg, positions=positions, impl=impl, chunk=chunk)
    u = u + a
    u = u + apply_mlp(p["mlp"], apply_norm(p["ln2"], u, cfg.norm), cfg.activation)
    out = h + u @ p["w_out_proj"]
    return (out, kv) if return_kv else out


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------


def init_decoder(key, cfg, dtype=jnp.float32):
    keys = jax.random.split(key, 8)
    params = {"embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype),
              "ln_f": norm_params(cfg, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)

    if cfg.layer_types:
        params["runs"] = [
            jax.vmap(lambda k, kind=kind: init_dense_layer(k, cfg, dtype, mixer=kind))(
                jax.random.split(jax.random.fold_in(keys[2], i), n))
            for i, (kind, n) in enumerate(layer_runs(cfg.layer_types))]
    elif cfg.family == "ssm":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_rwkv_layer(k, cfg, dtype))(lkeys)
    elif cfg.family == "hybrid":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_mamba_layer(k, cfg, dtype))(lkeys)
        params["shared"] = init_shared_attn_block(keys[3], cfg, dtype)
    elif cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_dense_layer(k, cfg, dtype))(lkeys)
        ckeys = jax.random.split(keys[3], n_cross)
        params["cross_layers"] = jax.vmap(lambda k: init_cross_layer(k, cfg, dtype))(ckeys)
    elif cfg.is_moe:
        kd = cfg.first_k_dense
        if kd:
            dkeys = jax.random.split(keys[2], kd)
            params["dense_layers"] = jax.vmap(
                lambda k: init_dense_layer(k, cfg, dtype, d_ff=cfg.dense_d_ff or cfg.d_ff))(dkeys)
        mkeys = jax.random.split(keys[3], cfg.n_layers - kd)
        params["layers"] = jax.vmap(lambda k: init_dense_layer(k, cfg, dtype, moe=True))(mkeys)
        if cfg.n_mtp_modules:
            k1, k2 = jax.random.split(keys[4])
            params["mtp"] = {
                "proj": dense_init(k1, 2 * cfg.d_model, cfg.d_model, dtype),
                "block": init_dense_layer(k2, cfg, dtype, moe=False, d_ff=cfg.dense_d_ff or cfg.d_ff),
                "ln": norm_params(cfg, dtype),
            }
    else:  # dense
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_dense_layer(k, cfg, dtype))(lkeys)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def layer_runs(layer_types):
    """(kind, count) of each run of consecutive layers of one kind."""
    return [(kind, len(list(run))) for kind, run in itertools.groupby(layer_types)]


def _publish_layers(runs) -> None:
    """Set the ``hybrid_layers{kind}`` gauge: the layers of each kind the
    model's parameter runs hold."""
    held = {}
    for p in runs:
        kind = "mamba" if "mamba" in p else "attention"
        held[kind] = held.get(kind, 0) + p["ln1"]["scale"].shape[0]
    gauge = obs.get_telemetry().metrics.gauge(
        "hybrid_layers", "layers of each kind the model holds")
    for kind, n in held.items():
        gauge.labels(kind=kind).set(n)


def _logits(params, cfg, h):
    h = apply_norm(params["ln_f"], h, cfg.norm)
    if cfg.tie_embeddings:
        # Reshard the (d-sharded) lookup table to vocab-sharded before the
        # head matmul: contraction over a tp-sharded d would otherwise make
        # XLA build full-vocab partial logits + a logits-sized all-reduce.
        w = shard(params["embed"], "tp", None).T
    else:
        w = params["lm_head"]
    logits = h @ w.astype(h.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard(logits, "batch", "seq", "tp")


def forward_decoder(params, cfg, tokens, *, image_embed=None, audio_embed=None,
                    impl="chunked", chunk=1024, remat="none", return_cache=False,
                    moe_cf=1.25):
    """Returns (logits, aux) or (logits, aux, cache) when return_cache."""
    B, S = tokens.shape
    h = embed_tokens(params["embed"], tokens)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    aux = jnp.zeros((), jnp.float32)
    caches = None

    if cfg.layer_types:
        assert not return_cache, "a layer pattern has no serving path"
        _publish_layers(params["runs"])
        block = _maybe_remat(functools.partial(
            dense_block, cfg=cfg, positions=positions, impl=impl, chunk=chunk), remat)

        def run_body(hh, lp):
            return block(lp, hh)[0], None

        for run in params["runs"]:
            h, _ = jax.lax.scan(run_body, h, run)
    elif cfg.family == "ssm":
        assert not return_cache, "use prefill_decoder for SSM caches"
        block = _maybe_remat(functools.partial(rwkv_block, cfg=cfg), remat)

        def body(carry, lp):
            return block(lp, carry), None

        h, _ = jax.lax.scan(body, h, params["layers"])
    elif cfg.family == "hybrid":
        assert not return_cache, "use prefill_decoder for hybrid caches"
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]), params["layers"])
        emb0 = h
        mblock = _maybe_remat(functools.partial(mamba_block, cfg=cfg), remat)

        def group(carry, glp):
            hh = shared_attn_apply(params["shared"], carry, emb0, cfg, impl,
                                   chunk, positions)

            def inner(c, lp):
                return mblock(lp, c), None

            hh, _ = jax.lax.scan(inner, hh, glp)
            return hh, None

        h, _ = jax.lax.scan(group, h, stacked)
    elif cfg.family == "vlm":
        every = cfg.cross_attn_every
        n_cross = cfg.n_layers // every
        self_stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((n_cross, every) + a.shape[1:]), params["layers"])
        block = _maybe_remat(
            functools.partial(dense_block, cfg=cfg, positions=positions, impl=impl,
                              chunk=chunk, return_kv=return_cache), remat)

        def group(carry, xs):
            hh, aux_c = carry
            slp, clp = xs

            def inner(c, lp):
                h2, a2 = c
                if return_cache:
                    h3, a3, kv = block(lp, h2)
                    return (h3, a2 + a3), kv
                h3, a3 = block(lp, h2)
                return (h3, a2 + a3), None

            (hh, aux_c), self_kv = jax.lax.scan(inner, (hh, aux_c), slp)
            if return_cache:
                hh, ckv = cross_block(clp, hh, cfg, image_embed, return_kv=True)
                return (hh, aux_c), (self_kv, ckv)
            hh = cross_block(clp, hh, cfg, image_embed)
            return (hh, aux_c), None

        (h, aux), kvs = jax.lax.scan(group, (h, aux), (self_stacked, params["cross_layers"]))
        if return_cache:
            self_kv, cross_kv = kvs
            caches = {"self": jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), self_kv),
                "cross": cross_kv}
    else:  # dense / moe
        block = _maybe_remat(
            functools.partial(dense_block, cfg=cfg, positions=positions, impl=impl,
                              chunk=chunk, return_kv=return_cache, moe_cf=moe_cf), remat)

        def body(carry, lp):
            hh, aux_c = carry
            if return_cache:
                h2, a2, kv = block(lp, hh)
                return (h2, aux_c + a2), kv
            h2, a2 = block(lp, hh)
            return (h2, aux_c + a2), None

        kv_parts = []
        if cfg.is_moe and cfg.first_k_dense:
            (h, aux), kv0 = jax.lax.scan(body, (h, aux), params["dense_layers"])
            kv_parts.append(kv0)
        (h, aux), kv1 = jax.lax.scan(body, (h, aux), params["layers"])
        kv_parts.append(kv1)
        if return_cache:
            if len(kv_parts) > 1:
                caches = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b], 0), kv_parts[0], kv_parts[1])
            else:
                caches = kv_parts[0]

    if return_cache:
        # prefill semantics: only the last position's logits are needed
        logits = _logits(params, cfg, h[:, -1:])
        return logits, aux, (h, caches)
    logits = _logits(params, cfg, h)
    return logits, aux, h


def _last_logits(params, cfg, h, last_pos=None):
    """Logits of the last *valid* prompt position. ``last_pos=None`` means the
    final position; an index (host int or traced scalar) selects earlier —
    the bucketed-prefill case, where the prompt is right-padded to a bucket
    length and causality keeps every position < true length unaffected."""
    if last_pos is None:
        return _logits(params, cfg, h[:, -1:])
    return _logits(params, cfg, jax.lax.dynamic_slice_in_dim(
        h, jnp.asarray(last_pos, jnp.int32), 1, 1))


def prefill_decoder(params, cfg, tokens, *, image_embed=None, audio_embed=None,
                    impl="chunked", chunk=1024, moe_cf=1.25, last_pos=None):
    """Single-pass prefill: returns (logits, cache) with per-layer caches/states.

    ``last_pos`` supports bucketed admission: prompts padded up to a bucket
    length still report the logits of their true last token.
    """
    _check_served(cfg)
    if cfg.family not in ("ssm", "hybrid"):
        logits, aux, (h, caches) = forward_decoder(
            params, cfg, tokens, image_embed=image_embed, audio_embed=audio_embed,
            impl=impl, chunk=chunk, return_cache=True, moe_cf=moe_cf)
        if last_pos is not None:
            logits = _last_logits(params, cfg, h, last_pos)
        return logits, caches

    if last_pos is not None:
        # recurrent families carry the padded positions *through their
        # state* — a right-padded prompt corrupts it, so there is no valid
        # last_pos semantics to offer; fail loudly over a silent wrong token
        raise ValueError(f"last_pos (bucketed prefill) is not supported for "
                         f"the {cfg.family!r} family: recurrent state would "
                         f"absorb the padding")

    B, S = tokens.shape
    h = embed_tokens(params["embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    if cfg.family == "ssm":
        states = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a, i=i: a[i], params["layers"])
            t, S_fin, last_t = ssm.rwkv6_tmix(lp["tmix"], apply_norm(lp["ln1"], h, cfg.norm),
                                              cfg, return_state=True)
            h = h + t
            c, last_c = ssm.rwkv6_cmix(lp["cmix"], apply_norm(lp["ln2"], h, cfg.norm))
            h = h + c
            states.append({"S": S_fin, "prev_t": last_t, "prev_c": last_c})
        cache = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *states)
        return _logits(params, cfg, h[:, -1:]), cache

    # hybrid (zamba2)
    emb0 = h
    mstates, skvs = [], []
    for i in range(cfg.n_layers):
        if cfg.shared_attn_every and i % cfg.shared_attn_every == 0:
            h, kv = shared_attn_apply(params["shared"], h, emb0, cfg, impl, chunk,
                                      positions, return_kv=True)
            skvs.append(kv)
        lp = jax.tree_util.tree_map(lambda a, i=i: a[i], params["layers"])
        m, st = ssm.mamba2_forward(lp["mamba"], apply_norm(lp["ln"], h, cfg.norm),
                                   cfg, return_state=True)
        h = h + m
        mstates.append(st)
    cache = {
        "mamba": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *mstates),
        "shared_kv": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *skvs),
    }
    return _logits(params, cfg, h[:, -1:]), cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_decoder(params, cfg, batch, *, impl="chunked", chunk=1024, remat="none",
                 moe_cf=1.25):
    tokens = batch["tokens"]
    logits, aux, h = forward_decoder(
        params, cfg, tokens, image_embed=batch.get("image_embed"),
        audio_embed=batch.get("audio_embed"), impl=impl, chunk=chunk, remat=remat,
        moe_cf=moe_cf)
    loss = cross_entropy(logits[:, :-1], tokens[:, 1:]) + aux
    if cfg.n_mtp_modules and "mtp" in params:
        # MTP (deepseek-v3): predict token t+2 from (h_t, emb(t+1))
        mtp = params["mtp"]
        emb_next = embed_tokens(params["embed"], tokens[:, 1:-1])
        u = jnp.concatenate([h[:, :-2], emb_next], axis=-1) @ mtp["proj"]
        B, S2 = tokens.shape[0], tokens.shape[1] - 2
        pos = jnp.broadcast_to(jnp.arange(S2, dtype=jnp.int32)[None], (B, S2))
        u, _ = dense_block(mtp["block"], u, cfg, pos, impl, chunk)
        mtp_logits = _logits(params, cfg, apply_norm(mtp["ln"], u, cfg.norm))
        loss = loss + 0.3 * cross_entropy(mtp_logits, tokens[:, 2:])
    return loss


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _check_served(cfg) -> None:
    """Refuse to serve what the cached paths do not compute: a layer
    pattern, or Granite's multipliers (the training path runs both)."""
    neutral = (cfg.embedding_multiplier == 1.0 and cfg.residual_multiplier == 1.0
               and cfg.logits_scaling == 1.0 and cfg.attention_multiplier is None)
    if cfg.layer_types or not neutral:
        raise ValueError(f"{cfg.name}: the serving paths run no layer pattern "
                         f"and no Granite multipliers")


def init_cache_decoder(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    _check_served(cfg)
    if cfg.family == "ssm":
        st = ssm.init_rwkv_state(cfg, batch, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape).copy(), st)
    if cfg.family == "hybrid":
        mst = ssm.init_mamba_state(cfg, batch, dtype)
        n_groups = cfg.n_layers // cfg.shared_attn_every
        kvshape = (n_groups, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
        return {
            "mamba": jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape).copy(), mst),
            "shared_kv": {"k": jnp.zeros(kvshape, dtype), "v": jnp.zeros(kvshape, dtype)},
        }
    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        cshape = (n_cross, batch, cfg.n_kv_heads, cfg.n_image_tokens, cfg.head_dim)
        self_c = init_gqa_cache(cfg, batch, max_len, dtype)
        return {
            "self": jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape).copy(), self_c),
            "cross": {"k": jnp.zeros(cshape, dtype), "v": jnp.zeros(cshape, dtype)},
        }
    percfg = init_mla_cache(cfg, batch, max_len, dtype) if cfg.use_mla else \
        init_gqa_cache(cfg, batch, max_len, dtype)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape).copy(), percfg)


def init_paged_cache_decoder(cfg, num_blocks: int, block_size: int,
                             dtype=jnp.bfloat16):
    """Paged KV layout for dense/moe: per-layer (num_blocks, ..., block_size,
    d) pools with a leading layer axis. One block-table row addresses the same
    physical block index in every layer's pool, so the table is shared
    across the stack."""
    _check_served(cfg)
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged KV cache needs a slotted-KV family, "
                         f"got {cfg.family!r}")
    per = init_mla_pool(cfg, num_blocks, block_size, dtype) if cfg.use_mla \
        else init_gqa_pool(cfg, num_blocks, block_size, dtype)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape).copy(), per)


def paged_prefill_step_decoder(params, cfg, cache, tokens, start, block_table,
                               *, last_pos=None, write: bool = True,
                               moe_cf=1.25):
    """One block-sized chunk of paged prefill for dense/moe stacks.

    tokens: (B, block_size) int32 — one chunk of the (right-padded) prompt;
    ``start`` (traced scalar) is its first virtual position, always a block
    multiple so the chunk occupies exactly one block-table column. KV is
    written straight into the (L, num_blocks, ..., block_size, d) pools through
    each layer's scatter — there is no contiguous (1, P, ...) prefill cache
    to splice afterwards. ``write=False`` recomputes activations against
    already-populated (prefix-hit) blocks without touching the pools.

    Returns (logits, cache): logits of position ``last_pos`` within the
    chunk (``None`` = final position), as :func:`_last_logits`.

    MoE note: routing capacity depends on the tokens routed together, so a
    chunk routes independently of the full-prompt pass; with a saturating
    capacity factor (no drops) the two are token-identical, otherwise
    chunked prefill may drop differently than contiguous prefill would.
    """
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged prefill needs a dense/moe KV cache, "
                         f"got {cfg.family!r}")
    h = embed_tokens(params["embed"], tokens)

    def make_body(moe_layer):
        def body(carry, xs):
            hh = carry
            lp, lcache = xs
            x = apply_norm(lp["ln1"], hh, cfg.norm)
            if cfg.use_mla:
                a, lnew = mla_prefill_paged(lp["attn"], x, lcache, start,
                                            block_table, cfg, write=write)
            else:
                a, lnew = gqa_prefill_paged(lp["attn"], x, lcache, start,
                                            block_table, cfg, write=write)
            hh = hh + a
            x = apply_norm(lp["ln2"], hh, cfg.norm)
            if moe_layer:
                m, _ = apply_moe(lp["moe"], x, cfg, capacity_factor=moe_cf)
            else:
                m = apply_mlp(lp["mlp"], x, cfg.activation)
            return hh + m, lnew

        return body

    if cfg.is_moe and cfg.first_k_dense:
        kd = cfg.first_k_dense
        cache_dense = jax.tree_util.tree_map(lambda a: a[:kd], cache)
        cache_moe = jax.tree_util.tree_map(lambda a: a[kd:], cache)
        h, new_dense = jax.lax.scan(make_body(False), h,
                                    (params["dense_layers"], cache_dense))
        h, new_moe = jax.lax.scan(make_body(True), h, (params["layers"], cache_moe))
        new_cache = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], 0), new_dense, new_moe)
    else:
        h, new_cache = jax.lax.scan(make_body(cfg.is_moe), h,
                                    (params["layers"], cache))

    return _last_logits(params, cfg, h, last_pos), new_cache


def decode_step_decoder(params, cfg, cache, tokens, cache_len, *, impl="chunked",
                        moe_cf=1.25, block_table=None):
    """One-token decode. tokens: (B,1) int32; cache_len: scalar or (B,) int32.

    ``impl="pallas"`` selects the fused single-query flash-decode kernel for
    every KV-cache attention in the stack; any other impl uses the naive
    decode oracle (the prefill/train impls chunked/pallas only apply to full
    sequence attention, so decode maps them onto {naive, pallas}).

    ``block_table`` (B, T) int32 switches the dense/moe KV path to the paged
    layout: ``cache`` leaves are (L, num_blocks, ..., block_size, d) pools and
    every layer resolves the same table row to its own pool.
    """
    B = tokens.shape[0]
    dimpl = "pallas" if impl == "pallas" else "naive"
    if block_table is not None and cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged decode needs a dense/moe KV cache, "
                         f"got {cfg.family!r}")
    h = embed_tokens(params["embed"], tokens)

    if cfg.family == "ssm":
        def body(carry, xs):
            hh = carry
            lp, st = xs
            t, S_new, prev_t = ssm.rwkv6_tmix_step(
                lp["tmix"], apply_norm(lp["ln1"], hh, cfg.norm), st["S"], st["prev_t"], cfg)
            hh = hh + t
            c, prev_c = ssm.rwkv6_cmix(lp["cmix"], apply_norm(lp["ln2"], hh, cfg.norm),
                                       prev=st["prev_c"])
            hh = hh + c
            return hh, {"S": S_new, "prev_t": prev_t, "prev_c": prev_c}

        h, new_cache = jax.lax.scan(body, h, (params["layers"], cache))
    elif cfg.family == "hybrid":
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]), params["layers"])
        mstacked = jax.tree_util.tree_map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]), cache["mamba"])
        emb0 = h

        def group(carry, xs):
            hh = carry
            glp, mst, skv = xs
            u = jnp.concatenate([hh, emb0], axis=-1) @ params["shared"]["w_in"]
            x = apply_norm(params["shared"]["ln1"], u, cfg.norm)
            a, skv_new = gqa_decode(params["shared"]["attn"], x, skv, cache_len, cfg,
                                    impl=dimpl)
            u = u + a
            u = u + apply_mlp(params["shared"]["mlp"],
                              apply_norm(params["shared"]["ln2"], u, cfg.norm), cfg.activation)
            hh = hh + u @ params["shared"]["w_out_proj"]

            def inner(c, xs2):
                lp, st = xs2
                m, st_new = ssm.mamba2_decode(lp["mamba"], apply_norm(lp["ln"], c, cfg.norm),
                                              st, cfg)
                return c + m, st_new

            hh, mst_new = jax.lax.scan(inner, hh, (glp, mst))
            return hh, (mst_new, skv_new)

        h, (mnew, snew) = jax.lax.scan(group, h, (stacked, mstacked, cache["shared_kv"]))
        new_cache = {
            "mamba": jax.tree_util.tree_map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), mnew),
            "shared_kv": snew,
        }
    elif cfg.family == "vlm":
        every = cfg.cross_attn_every
        n_cross = cfg.n_layers // every
        self_stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((n_cross, every) + a.shape[1:]), params["layers"])
        cache_stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((n_cross, every) + a.shape[1:]), cache["self"])

        def group(carry, xs):
            hh = carry
            slp, scache, clp, ckv = xs

            def inner(c, xs2):
                lp, lcache = xs2
                x = apply_norm(lp["ln1"], c, cfg.norm)
                a, lnew = gqa_decode(lp["attn"], x, lcache, cache_len, cfg, impl=dimpl)
                c = c + a
                c = c + apply_mlp(lp["mlp"], apply_norm(lp["ln2"], c, cfg.norm), cfg.activation)
                return c, lnew

            hh, snew = jax.lax.scan(inner, hh, (slp, scache))
            x = apply_norm(clp["ln1"], hh, cfg.norm)
            a, _ = gqa_decode(clp["attn"], x, None, cache_len, cfg,
                              cross_kv=(ckv["k"], ckv["v"]), impl=dimpl)
            hh = hh + jnp.tanh(clp["gate_attn"]) * a
            m = apply_mlp(clp["mlp"], apply_norm(clp["ln2"], hh, cfg.norm), cfg.activation)
            hh = hh + jnp.tanh(clp["gate_mlp"]) * m
            return hh, snew

        h, self_new = jax.lax.scan(
            group, h, (self_stacked, cache_stacked, params["cross_layers"], cache["cross"]))
        new_cache = {
            "self": jax.tree_util.tree_map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), self_new),
            "cross": cache["cross"],
        }
    else:  # dense / moe
        def make_body(moe_layer):
            def body(carry, xs):
                hh = carry
                lp, lcache = xs
                x = apply_norm(lp["ln1"], hh, cfg.norm)
                if cfg.use_mla:
                    if block_table is not None:
                        a, lnew = mla_decode_paged(lp["attn"], x, lcache,
                                                   cache_len, block_table, cfg,
                                                   impl=dimpl)
                    else:
                        a, lnew = mla_decode(lp["attn"], x, lcache, cache_len,
                                             cfg, impl=dimpl)
                elif block_table is not None:
                    a, lnew = gqa_decode_paged(lp["attn"], x, lcache, cache_len,
                                               block_table, cfg, impl=dimpl)
                else:
                    a, lnew = gqa_decode(lp["attn"], x, lcache, cache_len, cfg,
                                         impl=dimpl)
                hh = hh + a
                x = apply_norm(lp["ln2"], hh, cfg.norm)
                if moe_layer:
                    m, _ = apply_moe(lp["moe"], x, cfg, capacity_factor=moe_cf)
                else:
                    m = apply_mlp(lp["mlp"], x, cfg.activation)
                return hh + m, lnew

            return body

        if cfg.is_moe and cfg.first_k_dense:
            kd = cfg.first_k_dense
            cache_dense = jax.tree_util.tree_map(lambda a: a[:kd], cache)
            cache_moe = jax.tree_util.tree_map(lambda a: a[kd:], cache)
            h, new_dense = jax.lax.scan(make_body(False), h,
                                        (params["dense_layers"], cache_dense))
            h, new_moe = jax.lax.scan(make_body(True), h, (params["layers"], cache_moe))
            new_cache = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], 0), new_dense, new_moe)
        else:
            h, new_cache = jax.lax.scan(make_body(cfg.is_moe), h,
                                        (params["layers"], cache))

    logits = _logits(params, cfg, h)
    return logits, new_cache


def spec_decode_step_decoder(params, cfg, cache, tokens, cache_len, *,
                             impl="chunked", moe_cf=1.25, block_table=None):
    """Speculative verify step for dense/moe stacks.

    tokens: (B, S) int32 — the last accepted token followed by S-1 draft
    tokens; window position qi occupies cache slot cache_len + qi. One pass
    scores every draft: the returned logits are (B, S, V), where row qi is
    the target model's next-token distribution *given* the window prefix
    through position qi — row 0 scores the first draft token, row S-1 is
    the bonus distribution past the last draft. The KV cache comes back
    with all S positions written; the caller's accept/rollback is pure
    cache_len bookkeeping (rejected tail KVs are masked dead by later
    calls' lengths and overwritten in place by the next window).

    Recurrent families (ssm/hybrid) fold positions into their state, so a
    rejected draft cannot be rolled back by bookkeeping — refuse loudly.
    VLM/MLA can grow spec windows later; dense/moe GQA is the serving path.
    """
    dimpl = "pallas" if impl == "pallas" else "naive"
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"speculative decode needs a slotted-KV family, "
                         f"got {cfg.family!r}")
    if cfg.use_mla:
        raise ValueError("speculative decode is not implemented for MLA "
                         "attention (absorbed-q verify window pending)")
    h = embed_tokens(params["embed"], tokens)

    def make_body(moe_layer):
        def body(carry, xs):
            hh = carry
            lp, lcache = xs
            x = apply_norm(lp["ln1"], hh, cfg.norm)
            if block_table is not None:
                a, lnew = gqa_decode_spec_paged(lp["attn"], x, lcache,
                                                cache_len, block_table, cfg,
                                                impl=dimpl)
            else:
                a, lnew = gqa_decode_spec(lp["attn"], x, lcache, cache_len,
                                          cfg, impl=dimpl)
            hh = hh + a
            x = apply_norm(lp["ln2"], hh, cfg.norm)
            if moe_layer:
                m, _ = apply_moe(lp["moe"], x, cfg, capacity_factor=moe_cf)
            else:
                m = apply_mlp(lp["mlp"], x, cfg.activation)
            return hh + m, lnew

        return body

    if cfg.is_moe and cfg.first_k_dense:
        kd = cfg.first_k_dense
        cache_dense = jax.tree_util.tree_map(lambda a: a[:kd], cache)
        cache_moe = jax.tree_util.tree_map(lambda a: a[kd:], cache)
        h, new_dense = jax.lax.scan(make_body(False), h,
                                    (params["dense_layers"], cache_dense))
        h, new_moe = jax.lax.scan(make_body(True), h, (params["layers"], cache_moe))
        new_cache = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], 0), new_dense, new_moe)
    else:
        h, new_cache = jax.lax.scan(make_body(cfg.is_moe), h,
                                    (params["layers"], cache))

    logits = _logits(params, cfg, h)
    return logits, new_cache
