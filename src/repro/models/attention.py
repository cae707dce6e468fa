"""Attention: GQA / MHA / cross-attention / MLA, with KV-cache decode.

Three implementations of the core softmax-attention compute:
  naive   - materialize (Sq, Sk) scores; smoke tests + oracle
  chunked - flash-style online softmax over KV chunks in pure jnp; the
            dry-run/default path (never materializes Sq x Sk)
  pallas  - kernels/flash_attention.py fused fwd + custom_vjp flash backward
            (training-grade; TPU Mosaic target, interpret-mode on CPU).
            Selected per execution choice via MeshChoice.attn_impl.

Decode shards the KV cache sequence dim over the ``kvseq`` logical axis
(context-parallel decode): softmax over a sharded axis lowers to tiny
all-reduces of the per-shard max/denominator.

KV caches keep the sequence axis second to last: GQA caches are head-major,
(B, K, Smax, hd), and paged pools (num_blocks, K, block_size, hd), so a
decode kernel's KV tile is a (block, hd) slab that Mosaic's (8, 128) block
tiling accepts. MLA latent caches are (B, Smax, r) and (num_blocks,
block_size, r). Every scatter and gather below works on axis -2.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import apply_norm, apply_rope, dense_init
from repro.models.sharding import mesh_spec, shard

# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def gqa_params(key, cfg, dtype=jnp.float32, cross: bool = False):
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype,
                         scale=1.0 / math.sqrt(cfg.n_heads * hd)),
    }


def mla_params(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq_a": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "q_norm": {"scale": jnp.ones((cfg.q_lora_rank,), dtype)},
        "wq_b": dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_dim, dtype),
        "wkv_a": dense_init(ks[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype),
        "kv_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), dtype)},
        "wkv_b": dense_init(ks[3], cfg.kv_lora_rank,
                            cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype),
        "wo": dense_init(ks[4], cfg.n_heads * cfg.v_head_dim, cfg.d_model, dtype),
    }


def attn_params(key, cfg, dtype=jnp.float32, cross: bool = False):
    if cfg.use_mla and not cross:
        return mla_params(key, cfg, dtype)
    return gqa_params(key, cfg, dtype, cross=cross)


# ---------------------------------------------------------------------------
# core attention computations
# ---------------------------------------------------------------------------


def _group(q, n_kv):
    """(B,S,H,hd) -> (B,S,K,G,hd) grouped query heads."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


def naive_attention(q, k, v, *, causal: bool, q_offset=0, kv_len: Optional[jax.Array] = None,
                    scale: Optional[float] = None):
    """Oracle attention. q:(B,Sq,H,hd) k,v:(B,Sk,K,hd); ``scale`` is the
    softmax scale (default ``1/sqrt(hd)``).

    ``kv_len`` may be a scalar or a per-sequence (B,) vector (ragged decode
    under continuous batching); rows must keep kv_len >= 1 to stay
    well-defined — a fully-masked row softmaxes to uniform, not zero.
    ``q_offset`` may likewise be a (B,) vector: query row qi of sequence b
    then attends positions <= q_offset[b] + qi (the speculative-verify
    oracle, where each sequence's draft window starts at its own cache_len).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    qg = _group(q, K)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    Sk = k.shape[1]
    kv_idx = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        if jnp.ndim(q_offset) > 0:  # per-sequence window starts
            q_idx = jnp.arange(Sq)[None] + q_offset[:, None]     # (B, Sq)
            mask = kv_idx[None, None, :] <= q_idx[:, :, None]    # (B, Sq, Sk)
        else:
            q_idx = jnp.arange(Sq) + q_offset
            mask = kv_idx[None, :] <= q_idx[:, None]
    if kv_len is not None and jnp.ndim(kv_len) > 0:  # per-sequence lengths
        lenm = kv_idx[None, None, :] < kv_len[:, None, None]
        mask = (mask[None] if mask.ndim == 2 else mask) & lenm
    elif kv_len is not None:
        mask = mask & (kv_idx[None, :] < kv_len)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, q_offset=0, chunk: int = 1024,
                      scale: Optional[float] = None):
    """Flash-style online-softmax attention, scanning KV chunks.

    Never materializes the (Sq, Sk) score matrix; per-step live memory is
    O(Sq * chunk). This is the HLO the dry-run sees for prefill/train.

    Layout: everything runs in full-H (B, H, ...) form — GQA KV heads are
    broadcast to H *inside* each chunk — because the grouped (B, K, G, ...)
    layout cannot shard K=8 kv-heads over a 16-way tensor axis and forces the
    SPMD partitioner to replicate the scan carries (observed: 40GB+ carries).
    With full-H, every tensor shards (batch, tp) cleanly, including the
    online-softmax carries, which we constrain explicitly.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    vd = v.shape[-1]
    G = H // K
    Sk = k.shape[1]
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, K, vd), 1, 0)

    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)  # (B,H,Sq,hd)
    qh = qh / math.sqrt(hd) if scale is None else qh * scale
    qh = shard(qh, "batch", "tp", None, None)
    q_idx = jnp.arange(Sq) + q_offset

    def expand(blk):  # (B,chunk,K,d) -> (B,H,chunk,d)
        e = jnp.broadcast_to(blk.transpose(0, 2, 1, 3)[:, :, None],
                             (B, K, G, chunk, blk.shape[-1]))
        return e.reshape(B, H, chunk, blk.shape[-1])

    # bf16 score/probability tensors (fp32 online-softmax statistics and
    # accumulator) — standard TPU practice; halves the dominant HBM traffic
    # of the jnp fallback path. Toggled by the execution choice (hillclimb).
    lowp = os.environ.get("REPRO_ATTN_BF16", "0") == "1"

    def step(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, c = xs
        kdt = jnp.bfloat16 if lowp else jnp.float32
        kh = shard(expand(k_blk).astype(kdt), "batch", "tp", None, None)
        vh = shard(expand(v_blk).astype(kdt), "batch", "tp", None, None)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(kdt), kh,
                       preferred_element_type=jnp.float32)
        kv_idx = c * chunk + jnp.arange(chunk)
        mask = kv_idx[None, :] < Sk
        if causal:
            mask = mask & (kv_idx[None, :] <= q_idx[:, None])
        s = jnp.where(mask[None, None], s, -1e30)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = shard(l * corr + p.sum(-1), "batch", "tp", None)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(kdt), vh,
            preferred_element_type=jnp.float32)
        acc_new = shard(acc_new, "batch", "tp", None, None)
        return (shard(m_new, "batch", "tp", None), l_new, acc_new), None

    m0 = shard(jnp.full((B, H, Sq), -jnp.inf, jnp.float32), "batch", "tp", None)
    l0 = shard(jnp.zeros((B, H, Sq), jnp.float32), "batch", "tp", None)
    a0 = shard(jnp.zeros((B, H, Sq, vd), jnp.float32), "batch", "tp", None, None)
    # checkpoint each chunk step: the backward recomputes s/p per chunk
    # instead of saving O(Sq*Sk) probability residuals (flash-style backward)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0),
                                  (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3)  # (B,Sq,H,vd)
    return out.astype(q.dtype)


def attention_impl(q, k, v, *, causal, q_offset=0, impl: str = "chunked", chunk: int = 1024,
                   scale: Optional[float] = None):
    """Softmax attention by ``impl``; ``scale`` defaults to 1/sqrt(hd)."""
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset, scale=scale)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, q_offset=q_offset, chunk=chunk,
                                 scale=scale)
    if impl == "pallas":
        from repro.kernels import ops as kops

        def kernel(q, k, v):
            return kops.flash_attention(q, k, v, causal=causal,
                                        q_offset=q_offset, scale=scale)

        # XLA cannot partition a Mosaic kernel: under a mesh, run it per
        # shard, split over batch and KV heads (whole GQA groups per shard)
        spec = mesh_spec(k.shape, "batch", None, "tp", None)
        if spec is None:
            return kernel(q, k, v)
        return jax.shard_map(kernel, in_specs=(spec,) * 3, out_specs=spec,
                             check_vma=False)(q, k, v)
    raise ValueError(impl)


# ---------------------------------------------------------------------------
# GQA block forward (train / prefill / cross) and decode
# ---------------------------------------------------------------------------


def gqa_forward(p, x, cfg, *, positions=None, kv_x=None, causal=True,
                impl="chunked", chunk=1024, return_kv=False):
    """Self-attention (kv_x=None) or cross-attention (kv_x=encoder states)."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    src = x if kv_x is None else kv_x
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (src @ p["wk"]).reshape(B, src.shape[1], cfg.n_kv_heads, hd)
    v = (src @ p["wv"]).reshape(B, src.shape[1], cfg.n_kv_heads, hd)
    q = shard(q, "batch", "seq", "tp", None)
    k = shard(k, "batch", "seq", "tp", None)
    v = shard(v, "batch", "seq", "tp", None)
    if positions is not None and kv_x is None and cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = attention_impl(q, k, v, causal=causal and kv_x is None, impl=impl, chunk=chunk,
                         scale=cfg.attention_multiplier)
    out = shard(out, "batch", "seq", "tp", None)
    y = out.reshape(B, S, cfg.n_heads * hd) @ p["wo"]
    if return_kv:
        # head-major cache layout; sequence dim sharded over kvseq
        # (context-parallel decode) so the prefill scan's ys accumulator
        # shards too
        return y, {"k": shard(_heads_first(k), "batch", None, "kvseq", None),
                   "v": shard(_heads_first(v), "batch", None, "kvseq", None)}
    return y


def _heads_first(x):
    """(B, S, K, hd) <-> (B, K, S, hd): sequence-major activations to the
    head-major cache layout and back (the naive oracles take sequence-major
    KV)."""
    return jnp.swapaxes(x, 1, 2)


def init_gqa_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    hd = cfg.head_dim
    shape = (batch, cfg.n_kv_heads, max_seq, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_gqa_pool(cfg, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """Paged layout: KV blocks shared by all sequences, no batch dim."""
    shape = (num_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_mla_pool(cfg, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
    return {
        "latent": jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((num_blocks, block_size, cfg.qk_rope_head_dim), dtype),
    }


def _decode_positions(cache_len, B):
    """(B,1) rope positions from a scalar or per-sequence cache_len."""
    if jnp.ndim(cache_len) == 0:
        return jnp.full((B, 1), cache_len, jnp.int32)
    return cache_len.astype(jnp.int32)[:, None]


def _on_seq_axis(rows, ndim: int):
    """(B, Smax) per-position rows, shaped to broadcast against an
    ``ndim``-dim (B, ..., Smax, d) cache."""
    return rows.reshape((rows.shape[0],) + (1,) * (ndim - 3)
                        + (rows.shape[1], 1))


def _scatter_token(buf, new, cache_len):
    """Write ``new`` (B, ..., 1, d) into ``buf`` (B, ..., Smax, d) at seq
    position ``cache_len`` — scalar (lockstep decode, one dynamic slice) or
    per-sequence (B,) (continuous batching, one-hot masked select)."""
    if jnp.ndim(cache_len) == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), cache_len, buf.ndim - 2)
    onehot = jnp.arange(buf.shape[-2])[None] == cache_len[:, None]  # (B,Smax)
    return jnp.where(_on_seq_axis(onehot, buf.ndim), new.astype(buf.dtype),
                     buf)


def _scatter_token_paged(pool, new, cache_len, block_table):
    """Write ``new`` (B, ..., 1, d) into a block pool (num_blocks, ...,
    block_size, d) at virtual position ``cache_len`` of each sequence,
    routed through its block-table row. Idle serving slots' rows point at
    the null block, so their masked-garbage writes never touch a live
    sequence's cache."""
    bs = pool.shape[-2]
    B = new.shape[0]
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    blk = jnp.clip(cl // bs, 0, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(jnp.asarray(block_table, jnp.int32),
                               blk[:, None], 1)[:, 0]
    phys = jnp.clip(phys, 0, pool.shape[0] - 1)
    # advanced indices split by the head slice put B first: (B, ..., d)
    return pool.at[phys, ..., cl % bs, :].set(
        new[..., 0, :].astype(pool.dtype))


def _scatter_tokens(buf, new, cache_len):
    """Write ``new`` (B, ..., S, d) into ``buf`` (B, ..., Smax, d) at seq
    positions cache_len..cache_len+S-1 (the speculative draft window).
    Scalar cache_len is one dynamic slice; per-sequence (B,) routes each
    buffer position p to draft index p - cache_len[b] via a masked gather."""
    S = new.shape[-2]
    if jnp.ndim(cache_len) == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), cache_len, buf.ndim - 2)
    rel = jnp.arange(buf.shape[-2])[None] - cache_len[:, None]     # (B,Smax)
    idx = _on_seq_axis(jnp.clip(rel, 0, S - 1), buf.ndim)
    sel = jnp.take_along_axis(new.astype(buf.dtype), idx, axis=buf.ndim - 2)
    valid = _on_seq_axis((rel >= 0) & (rel < S), buf.ndim)
    return jnp.where(valid, sel, buf)


def _scatter_tokens_paged(pool, new, cache_len, block_table):
    """Write ``new`` (B, ..., S, d) into a block pool at virtual positions
    cache_len..cache_len+S-1 of each sequence. The window is at most a few
    tokens, so S single-position scatters (each one indexed pool update)
    beat building a multi-hot routing tensor over the whole pool."""
    cl = jnp.asarray(cache_len, jnp.int32)
    for i in range(new.shape[-2]):
        pool = _scatter_token_paged(pool, new[..., i:i + 1, :], cl + i,
                                    block_table)
    return pool


def _scatter_chunk_paged(pool, new, start, block_table):
    """Write one block-aligned chunk ``new`` (B, ..., block_size, d) into a
    block pool at virtual positions [start, start + block_size), routed
    through each sequence's block-table row. ``start`` may be traced (the
    chunked-prefill loop reuses one compile for every chunk index); it must
    be a multiple of block_size — the chunk grid *is* the block grid, which
    is what lets prefix-cache hits skip whole chunks exactly."""
    bs = pool.shape[-2]
    B = new.shape[0]
    blk_idx = jnp.clip(jnp.asarray(start, jnp.int32) // bs, 0,
                       block_table.shape[1] - 1)
    phys = jax.lax.dynamic_slice_in_dim(
        jnp.asarray(block_table, jnp.int32), blk_idx, 1, 1)[:, 0]
    phys = jnp.clip(phys, 0, pool.shape[0] - 1)
    return pool.at[phys].set(new.astype(pool.dtype))


def gqa_prefill_paged(p, x, cache, start, block_table, cfg, *,
                      write: bool = True):
    """One chunk of paged prefill: ingest block_size prompt positions
    starting at ``start`` straight into the KV pools, then attend causally
    over everything written so far (gathered through the block table).

    ``write=False`` is the full-prefix-hit path: every block is already
    populated (by the donor sequence that prefilled the identical prefix),
    so the chunk only *reads* the pools to recompute the last position's
    activations for logits — no pool mutation, shared blocks stay intact.

    Attention is the naive oracle: the flash kernel bakes ``q_offset`` into
    its index maps (static), which would force one compile per chunk index;
    a traced offset keeps the whole prefill at one compile. Prefill impl
    only affects ingestion — decode keeps its kernel selection.
    """
    from repro.paging import gather_paged_kv

    B, C, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, C, cfg.n_heads, hd)
    k_new = (x @ p["wk"]).reshape(B, C, cfg.n_kv_heads, hd)
    v_new = (x @ p["wv"]).reshape(B, C, cfg.n_kv_heads, hd)
    if cfg.pos_embedding == "rope":
        pos = jnp.asarray(start, jnp.int32) + jnp.arange(C, dtype=jnp.int32)
        pos = jnp.broadcast_to(pos[None], (B, C))
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    if write:
        ck = _scatter_chunk_paged(cache["k"], _heads_first(k_new), start,
                                  block_table)
        cv = _scatter_chunk_paged(cache["v"], _heads_first(v_new), start,
                                  block_table)
    else:
        ck, cv = cache["k"], cache["v"]
    out = naive_attention(q, _heads_first(gather_paged_kv(ck, block_table)),
                          _heads_first(gather_paged_kv(cv, block_table)),
                          causal=True, q_offset=start)
    y = out.reshape(B, C, cfg.n_heads * hd) @ p["wo"]
    return y, {"k": ck, "v": cv}


def mla_prefill_paged(p, x, cache, start, block_table, cfg, *,
                      write: bool = True):
    """One chunk of paged MLA prefill over latent pools.

    Ingests the chunk's normalized latent + rope key into the pools, then
    reconstructs per-head K/V from the gathered latents (the same
    ``latent @ wkv_b`` expansion :func:`mla_forward` uses, so chunked
    ingestion matches the contiguous prefill numerics) and attends causally
    with a traced ``q_offset``. ``write=False`` as in
    :func:`gqa_prefill_paged`: read-only recompute on a full prefix hit.
    """
    from repro.paging import gather_paged_kv

    B, C, _ = x.shape
    nope, v_dim = cfg.qk_nope_head_dim, cfg.v_head_dim
    pos = jnp.asarray(start, jnp.int32) + jnp.arange(C, dtype=jnp.int32)
    pos = jnp.broadcast_to(pos[None], (B, C))
    q_nope, q_rope, latent_new, k_rope_new = _mla_qkv(p, x, cfg, pos)
    if write:
        lat = _scatter_chunk_paged(cache["latent"], latent_new, start, block_table)
        kr = _scatter_chunk_paged(cache["k_rope"], k_rope_new, start, block_table)
    else:
        lat, kr = cache["latent"], cache["k_rope"]
    lat_g = gather_paged_kv(lat, block_table)  # (B, S, r)
    kr_g = gather_paged_kv(kr, block_table)    # (B, S, rope_d)
    S = lat_g.shape[1]
    kv = (lat_g.astype(jnp.float32) @ p["wkv_b"]).reshape(
        B, S, cfg.n_heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_g[:, :, None, :].astype(jnp.float32),
                                  (B, S, cfg.n_heads, kr_g.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = naive_attention(q, k, v, causal=True, q_offset=start)
    y = out.reshape(B, C, cfg.n_heads * v_dim) @ p["wo"]
    return y, {"latent": lat, "k_rope": kr}


def gqa_decode(p, x, cache, cache_len, cfg, *, cross_kv=None, impl: str = "naive"):
    """One-token decode. x: (B,1,d); cache k/v: (B,K,Smax,hd).

    ``cache_len``: scalar (all sequences in lockstep) or (B,) int32 (ragged
    continuous batching). ``impl``: ``naive`` materializes the (H, Smax)
    score rows; ``pallas`` runs the fused single-query flash-decode kernel
    that streams only cache_len-valid KV tiles once per GQA group.
    """
    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    if cross_kv is not None:
        k, v = cross_kv  # head-major, as the prefill returns them
        if impl == "pallas":
            from repro.kernels import ops as kops
            out = kops.decode_attention(q, k, v, k.shape[2])
        else:
            out = naive_attention(q, _heads_first(k), _heads_first(v),
                                  causal=False)
        new_cache = cache
    else:
        k_new = (x @ p["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
        v_new = (x @ p["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
        if cfg.pos_embedding == "rope":
            pos = _decode_positions(cache_len, B)
            q = apply_rope(q, pos, cfg.rope_theta)
            k_new = apply_rope(k_new, pos, cfg.rope_theta)
        ck = _scatter_token(cache["k"], _heads_first(k_new), cache_len)
        cv = _scatter_token(cache["v"], _heads_first(v_new), cache_len)
        ck = shard(ck, "batch", None, "kvseq", None)
        cv = shard(cv, "batch", None, "kvseq", None)
        new_cache = {"k": ck, "v": cv}
        if impl == "pallas":
            from repro.kernels import ops as kops
            out = kops.decode_attention(q, ck, cv, cache_len + 1)
        else:
            out = naive_attention(q, _heads_first(ck), _heads_first(cv),
                                  causal=False, kv_len=cache_len + 1)
    y = out.reshape(B, 1, cfg.n_heads * hd) @ p["wo"]
    return y, new_cache


def gqa_decode_paged(p, x, cache, cache_len, block_table, cfg, *,
                     impl: str = "naive"):
    """One-token GQA decode over a paged KV cache.

    cache: {"k","v"} pools of shape (num_blocks, K, block_size, hd) shared by
    every sequence; ``block_table`` (B, T) int32 names each sequence's
    blocks. Math is identical to :func:`gqa_decode` on the contiguous cache
    the table describes: scatter the new token's KV at virtual position
    ``cache_len``, then attend over positions < cache_len + 1. ``naive``
    gathers the contiguous view through the table (the oracle); ``pallas``
    streams physical blocks directly via the block-table flash-decode kernel.
    """
    from repro.paging import gather_paged_kv

    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    k_new = (x @ p["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
    v_new = (x @ p["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.pos_embedding == "rope":
        pos = _decode_positions(cache_len, B)
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    ck = _scatter_token_paged(cache["k"], _heads_first(k_new), cache_len,
                              block_table)
    cv = _scatter_token_paged(cache["v"], _heads_first(v_new), cache_len,
                              block_table)
    new_cache = {"k": ck, "v": cv}
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.decode_attention_paged(q, ck, cv, block_table, cache_len + 1)
    else:
        out = naive_attention(q, _heads_first(gather_paged_kv(ck, block_table)),
                              _heads_first(gather_paged_kv(cv, block_table)),
                              causal=False, kv_len=cache_len + 1)
    y = out.reshape(B, 1, cfg.n_heads * hd) @ p["wo"]
    return y, new_cache


def gqa_decode_spec(p, x, cache, cache_len, cfg, *, impl: str = "naive"):
    """Speculative multi-token decode: verify S draft positions in one pass.

    x: (B,S,d) — the last accepted token followed by S-1 draft tokens, so
    position qi of the window sits at cache slot cache_len + qi. All S
    tokens' KV are scattered into the cache (rollback is the *caller's*
    cache_len bookkeeping: rejected tail KVs stay resident but are masked
    dead by every later call's length arguments), then each position
    attends causally inside the window on top of its sequence's history:
    positions < cache_len + qi + 1. Returns (B,S,d) activations — the
    logits at window position qi score draft token qi+1, exactly the
    verify distribution rejection sampling needs.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k_new = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v_new = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.pos_embedding == "rope":
        pos = _decode_positions(cache_len, B) + jnp.arange(S, dtype=jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    ck = _scatter_tokens(cache["k"], _heads_first(k_new), cache_len)
    cv = _scatter_tokens(cache["v"], _heads_first(v_new), cache_len)
    ck = shard(ck, "batch", None, "kvseq", None)
    cv = shard(cv, "batch", None, "kvseq", None)
    new_cache = {"k": ck, "v": cv}
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.decode_attention_spec(q, ck, cv, cache_len)
    else:
        out = naive_attention(q, _heads_first(ck), _heads_first(cv),
                              causal=True, q_offset=cache_len)
    y = out.reshape(B, S, cfg.n_heads * hd) @ p["wo"]
    return y, new_cache


def gqa_decode_spec_paged(p, x, cache, cache_len, block_table, cfg, *,
                          impl: str = "naive"):
    """Speculative multi-token decode over a paged KV cache.

    Same verify-window math as :func:`gqa_decode_spec`; the S draft KVs are
    scattered through the block table (the engine appends boundary blocks
    for positions cache_len..cache_len+S-1 before the call), and rollback is
    again pure cache_len bookkeeping — rejected positions' blocks stay
    mapped, their stale contents masked dead and overwritten by the next
    window.
    """
    from repro.paging import gather_paged_kv

    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k_new = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v_new = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.pos_embedding == "rope":
        pos = _decode_positions(cache_len, B) + jnp.arange(S, dtype=jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    ck = _scatter_tokens_paged(cache["k"], _heads_first(k_new), cache_len,
                               block_table)
    cv = _scatter_tokens_paged(cache["v"], _heads_first(v_new), cache_len,
                               block_table)
    new_cache = {"k": ck, "v": cv}
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.decode_attention_spec_paged(q, ck, cv, block_table,
                                               cache_len)
    else:
        out = naive_attention(q, _heads_first(gather_paged_kv(ck, block_table)),
                              _heads_first(gather_paged_kv(cv, block_table)),
                              causal=True, q_offset=cache_len)
    y = out.reshape(B, S, cfg.n_heads * hd) @ p["wo"]
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v3): latent KV cache; decode uses the absorbed form
# ---------------------------------------------------------------------------

_MLA_PALLAS_WARNED = False  # one-time impl-fallback warning (mla_forward)


def _mla_qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = apply_norm(p["q_norm"], x @ p["wq_a"], "rmsnorm") @ p["wq_b"]
    q = q.reshape(B, S, cfg.n_heads, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv_a = x @ p["wkv_a"]
    latent = apply_norm(p["kv_norm"], kv_a[..., :cfg.kv_lora_rank], "rmsnorm")
    k_rope = kv_a[..., cfg.kv_lora_rank:].reshape(B, S, 1, rope_d)
    if positions is not None:
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, latent, k_rope[..., 0, :]


def mla_forward(p, x, cfg, *, positions, impl="chunked", chunk=1024, return_cache=False):
    B, S, _ = x.shape
    nope, v_dim = cfg.qk_nope_head_dim, cfg.v_head_dim
    if impl == "pallas" and nope + cfg.qk_rope_head_dim != v_dim:
        # the fused MHA kernel assumes one head dim for q/k/v; MLA's qk dim
        # (nope + rope) differs from v_dim, so route prefill/training through
        # the chunked online-softmax path instead of producing garbage.
        # (MLA *decode* has its own latent-space pallas kernel and is fine.)
        import warnings
        global _MLA_PALLAS_WARNED
        if not _MLA_PALLAS_WARNED:
            _MLA_PALLAS_WARNED = True
            warnings.warn(
                f"MLA prefill cannot use impl='pallas' (qk head dim "
                f"{nope + cfg.qk_rope_head_dim} != v head dim {v_dim}); "
                f"falling back to 'chunked'. Decode still uses the fused "
                f"latent-space kernel.", RuntimeWarning, stacklevel=2)
        impl = "chunked"
    q_nope, q_rope, latent, k_rope = _mla_qkv(p, x, cfg, positions)
    kv = (latent @ p["wkv_b"]).reshape(B, S, cfg.n_heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, cfg.n_heads, k_rope.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    q = shard(q, "batch", "seq", "tp", None)
    k = shard(k, "batch", "seq", "tp", None)
    v = shard(v, "batch", "seq", "tp", None)
    out = attention_impl(q, k, v, causal=True, impl=impl, chunk=chunk)
    out = out.reshape(B, S, cfg.n_heads * v_dim)
    y = out @ p["wo"]
    if return_cache:
        return y, {"latent": shard(latent, "batch", "kvseq", None),
                   "k_rope": shard(k_rope, "batch", "kvseq", None)}
    return y


def init_mla_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return {
        "latent": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
    }


def _mla_naive_latent_ctx(q_lat, q_rope, lat, kr, kv_len, scale):
    """Latent-space attention oracle shared by the contiguous and paged
    decode paths: scores = q_lat . latent + q_rope . k_rope, values = latent.
    Returns the (B, 1, H, r) context."""
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, lat.astype(jnp.float32))
         + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                      kr.astype(jnp.float32))) * scale
    kv_idx = jnp.arange(lat.shape[1])
    if jnp.ndim(kv_len) > 0:  # ragged continuous batch
        valid = (kv_idx[None] < kv_len[:, None])[:, None, None]
    else:
        valid = (kv_idx < kv_len)[None, None, None]
    s = jnp.where(valid, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bsr->bqhr", probs, lat.astype(jnp.float32))


def _mla_absorbed_q(p, q_nope, cfg):
    """Absorb W_UK into the query; returns (q_lat, w_uv)."""
    nope, v_dim = cfg.qk_nope_head_dim, cfg.v_head_dim
    wkv_b = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, nope + v_dim)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    # (B,1,H,nope) x (r,H,nope) -> (B,1,H,r)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    return q_lat, w_uv


def mla_decode(p, x, cache, cache_len, cfg, *, impl: str = "naive"):
    """Absorbed-matrix MLA decode: attention runs in the latent space.

    scores = q_nope . W_UK^T . latent  +  q_rope . k_rope
    out    = (probs . latent) . W_UV -> wo
    The KV cache is only (kv_lora_rank + rope_dim) wide per position.
    ``cache_len`` scalar or (B,); ``impl="pallas"`` routes the latent-space
    attention through the fused single-query kernel (K=1, G=H).
    """
    B = x.shape[0]
    nope, v_dim, rope_d = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.qk_rope_head_dim
    pos = _decode_positions(cache_len, B)
    q_nope, q_rope, latent_new, k_rope_new = _mla_qkv(p, x, cfg, pos)

    lat = _scatter_token(cache["latent"], latent_new, cache_len)
    kr = _scatter_token(cache["k_rope"], k_rope_new, cache_len)
    lat = shard(lat, "batch", "kvseq", None)
    kr = shard(kr, "batch", "kvseq", None)

    q_lat, w_uv = _mla_absorbed_q(p, q_nope, cfg)
    scale = 1.0 / math.sqrt(nope + rope_d)
    if impl == "pallas":
        from repro.kernels import ops as kops
        ctx = kops.decode_attention_mla(
            q_lat, q_rope.astype(jnp.float32), lat, kr, cache_len + 1,
            scale=scale).astype(jnp.float32)
    else:
        ctx = _mla_naive_latent_ctx(q_lat, q_rope, lat, kr, cache_len + 1, scale)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32)).astype(x.dtype)
    y = out.reshape(B, 1, cfg.n_heads * v_dim) @ p["wo"]
    return y, {"latent": lat, "k_rope": kr}


def mla_decode_paged(p, x, cache, cache_len, block_table, cfg, *,
                     impl: str = "naive"):
    """Absorbed-matrix MLA decode over paged latent pools.

    cache: {"latent": (num_blocks, block_size, r),
            "k_rope": (num_blocks, block_size, rd)} shared physical blocks;
    ``block_table`` (B, T) int32. Same latent-space math as
    :func:`mla_decode`, with the per-sequence cache reached through the
    table (gathered for ``naive``, scalar-prefetched for ``pallas``).
    """
    from repro.paging import gather_paged_kv

    B = x.shape[0]
    nope, v_dim, rope_d = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.qk_rope_head_dim
    pos = _decode_positions(cache_len, B)
    q_nope, q_rope, latent_new, k_rope_new = _mla_qkv(p, x, cfg, pos)

    lat = _scatter_token_paged(cache["latent"], latent_new, cache_len, block_table)
    kr = _scatter_token_paged(cache["k_rope"], k_rope_new, cache_len, block_table)

    q_lat, w_uv = _mla_absorbed_q(p, q_nope, cfg)
    scale = 1.0 / math.sqrt(nope + rope_d)
    if impl == "pallas":
        from repro.kernels import ops as kops
        ctx = kops.decode_attention_mla_paged(
            q_lat, q_rope.astype(jnp.float32), lat, kr, block_table,
            cache_len + 1, scale=scale).astype(jnp.float32)
    else:
        ctx = _mla_naive_latent_ctx(
            q_lat, q_rope, gather_paged_kv(lat, block_table),
            gather_paged_kv(kr, block_table), cache_len + 1, scale)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32)).astype(x.dtype)
    y = out.reshape(B, 1, cfg.n_heads * v_dim) @ p["wo"]
    return y, {"latent": lat, "k_rope": kr}
