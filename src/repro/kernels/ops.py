"""Jit'd dispatch wrappers for the Pallas kernels.

Backend selection is automatic (kernels/backend.py): on a real TPU the
kernels lower via Mosaic; everywhere else they run in interpret mode. The
model code calls these through ``impl="pallas"`` switches; the default
dry-run path uses the pure-jnp implementations so the 512-host-device AOT
compile never lowers Mosaic ops.

All wrappers here are differentiable: flash_attention and rmsnorm carry
``jax.custom_vjp`` backward kernels, so ``jax.grad`` through a pallas model
never materializes an (Sq, Sk) tensor or an unfused norm backward.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref  # noqa: F401  (oracles re-exported for callers)
from repro.kernels.backend import default_interpret as _interpret  # noqa: F401
from repro.kernels.depthwise_conv import depthwise_conv as _dw
from repro.kernels.flash_attention import (flash_attention_mha, flash_decode,
                                           flash_decode_paged,
                                           flash_decode_spec,
                                           flash_decode_spec_paged)
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


def rmsnorm(x, scale, eps: float = 1e-5):
    return _rmsnorm(x, scale, eps=eps)


def depthwise_conv(x, w):
    return _dw(x, w)


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    scale=None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,K,hd) with K dividing H (GQA broadcast);
    ``scale`` is the softmax scale (default ``1/sqrt(hd)``).

    The head broadcast is a reshape of a broadcast_to — its transpose is a
    sum over the query-head group axis, which is exactly how dk/dv for a
    shared KV head accumulate over the G query heads that attended through
    it. The MHA kernel itself never sees GQA.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    if K != H:
        G = H // K
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (B, Sk, K, G, hd)).reshape(B, Sk, H, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (B, Sk, K, G, hd)).reshape(B, Sk, H, hd)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_mha(qt, kt, vt, causal=causal, q_offset=q_offset,
                              scale=scale)
    return out.transpose(0, 2, 1, 3)


def decode_attention(q, k, v, lengths, *, block_k: int = 256):
    """Single-token GQA decode against a ragged KV cache, fused.

    q: (B, 1, H, hd) — the new token's queries (cache already updated).
    k,v: (B, K, Smax, hd) head-major cache buffers; lengths: (B,) or scalar
    valid counts.

    Unlike the prefill wrapper above, the KV heads are NOT broadcast to H —
    the kernel's query block holds the whole (G = H//K) query group, so each
    cache tile is streamed once per KV head. That is the decode win: the
    bytes moved per token drop from H/K x cache to 1 x cache.
    """
    B, _, H, hd = q.shape
    K = k.shape[1]
    qg = q.reshape(B, K, H // K, hd)  # (B,1,H,hd) -> grouped, same head order
    out = flash_decode(qg, k, v, lengths, block_k=block_k)
    return out.reshape(B, 1, H, v.shape[-1])


def decode_attention_mla(q_lat, q_rope, latent, k_rope, lengths, *,
                         scale: float, block_k: int = 256):
    """Absorbed-matrix MLA decode in the latent space, fused.

    q_lat: (B, 1, H, r) query absorbed through W_UK; q_rope: (B, 1, H, rd).
    latent: (B, Smax, r) cache; k_rope: (B, Smax, rd) cache.

    Keys are the concatenation [latent | k_rope] and values are the latent
    itself, so the same single-query kernel runs with K=1, G=H and an
    explicit softmax scale (1/sqrt(nope+rope), not 1/sqrt(r+rope)). Returns
    the latent-space context (B, 1, H, r); the caller applies W_UV.
    """
    q = jnp.concatenate([q_lat, q_rope], -1)  # (B, K=1, G=H, r+rd)
    kv = jnp.concatenate([latent, k_rope.astype(latent.dtype)], -1)[:, None]
    val = latent[:, None]
    return flash_decode(q, kv, val, lengths, scale=scale, block_k=block_k)


def decode_attention_paged(q, k_pool, v_pool, block_table, lengths):
    """Single-token GQA decode against a paged (block-pooled) KV cache.

    q: (B, 1, H, hd); k_pool/v_pool: (num_blocks, K, block_size, hd[v])
    shared physical blocks; block_table: (B, T) int32; lengths: (B,) or
    scalar valid counts. Same grouped-query streaming as
    ``decode_attention``, with the KV index maps going through the
    scalar-prefetched block table.
    """
    B, _, H, hd = q.shape
    K = k_pool.shape[1]
    qg = q.reshape(B, K, H // K, hd)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    out = flash_decode_paged(qg, k_pool, v_pool, block_table, lengths)
    return out.reshape(B, 1, H, v_pool.shape[-1])


def decode_attention_spec(q, k, v, lengths, *, block_k: int = 256):
    """Speculative multi-token GQA verify against a ragged KV cache, fused.

    q: (B, S, H, hd) — the S draft positions' queries (draft KVs already
    scattered at positions lengths[b]..lengths[b]+S-1). k,v: (B, K, Smax,
    hd[v]) head-major cache buffers; lengths: (B,) or scalar BASE valid
    counts (before the drafts). Draft position qi attends cache positions
    < lengths[b] + qi + 1 — causal inside the verify tile. One kernel call
    verifies all S positions; the cache bytes are still streamed once per KV
    head, amortized over S tokens instead of one.
    """
    B, S, H, hd = q.shape
    K = k.shape[1]
    # (B,S,H,hd) -> (B,K,S,G,hd): group the G heads sharing each KV head,
    # keeping draft order explicit so the kernel maps row r -> qi = r // G
    qg = q.reshape(B, S, K, H // K, hd).transpose(0, 2, 1, 3, 4)
    out = flash_decode_spec(qg, k, v, lengths, block_k=block_k)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, v.shape[-1])


def decode_attention_spec_paged(q, k_pool, v_pool, block_table, lengths):
    """Speculative multi-token GQA verify against a paged KV cache.

    q: (B, S, H, hd); pools/table/lengths as in ``decode_attention_paged``,
    with ``lengths`` the BASE valid counts and the block table covering the
    draft positions (boundary blocks appended before the verify call).
    """
    B, S, H, hd = q.shape
    K = k_pool.shape[1]
    qg = q.reshape(B, S, K, H // K, hd).transpose(0, 2, 1, 3, 4)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    out = flash_decode_spec_paged(qg, k_pool, v_pool, block_table, lengths)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, v_pool.shape[-1])


def decode_attention_mla_paged(q_lat, q_rope, latent_pool, k_rope_pool,
                               block_table, lengths, *, scale: float):
    """Absorbed-matrix MLA decode over paged latent pools.

    latent_pool: (num_blocks, block_size, r); k_rope_pool: (..., rd).
    Keys are [latent | k_rope] per block, values the latent itself — the
    paged kernel runs with K=1, G=H exactly like the contiguous MLA path.
    """
    B = q_lat.shape[0]
    q = jnp.concatenate([q_lat, q_rope], -1)  # (B, K=1, G=H, r+rd)
    kv = jnp.concatenate(
        [latent_pool, k_rope_pool.astype(latent_pool.dtype)], -1)[:, None]
    val = latent_pool[:, None]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    return flash_decode_paged(q, kv, val, block_table, lengths, scale=scale)
