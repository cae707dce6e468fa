"""Flash-attention Pallas kernels: fused forward + custom_vjp backward.

This is the training-grade attention hot path (``impl="pallas"``). Three
kernels share one tiling scheme:

  forward   grid (B, H, q_blocks, kv_blocks)   -> o, lse
  dq        grid (B, H, q_blocks, kv_blocks)   -> dq
  dkv       grid (B, H, kv_blocks, q_blocks)   -> dk, dv

Tiling / residual layout
------------------------
The innermost grid dimension iterates sequentially ("arbitrary" semantics on
TPU), so the online-softmax state — running max ``m``, normalizer ``l`` and
the output accumulator — lives in VMEM scratch that carries across KV tiles
of one query block. KV streams through the grid via BlockSpec index maps in
(block_k, hd) tiles: per grid step the kernel holds one (block_q, hd) query
tile and one (block_k, hd) KV tile, and the (Sq, Sk) score matrix never
exists anywhere — not in HBM, not in VMEM. The forward additionally emits the
log-sum-exp rows ``lse = m + log(l)``; the backward keeps them as (B, H, Sq)
and, with the saved output ``o``, that is the entire residual, O(B*H*Sq)
instead of the O(Sq*Sk) probability matrix. Mosaic tiles a block's last two
dims by (8, 128), so inside the kernels the per-row statistics (lse, and
the backward's ``delta``) travel lane-broadcast as (B, H, Sq, 128).

Causal block skipping
---------------------
For causal attention, KV tiles entirely above the diagonal contribute
nothing. Their grid steps are predicated out with ``pl.when`` AND their
BlockSpec index maps clamp to the last needed tile, so the pipeline re-fetches
a resident block instead of DMA-ing a dead one — the compute drops from
Sq*Sk to the ~Sq*Sk/2 lower-triangular FLOPs (the paper-shape win at long
Sq). The dkv kernel mirrors this by skipping query tiles entirely *below*
its KV tile's diagonal band.

Backward derivation (AttentionEngine-style online recomputation): with
``p = exp(s - lse)`` and ``delta = rowsum(do * o)``,

  dv = p^T @ do
  ds = p * (do @ v^T - delta)
  dq = scale * ds @ k          (accumulated over KV tiles)
  dk = scale * ds^T @ q        (accumulated over Q tiles)

``jax.custom_vjp`` wires these in so ``jax.grad`` through ``impl="pallas"``
never differentiates the Pallas forward. Heads are pre-broadcast by the
wrapper (GQA handled in ops.py, whose broadcast transpose sums dk/dv over the
query-head group).

Decode kernel
-------------
``flash_decode`` is the serving-path sibling: one query *token* per (batch,
kv-head) program, grid (B, K, kv_blocks). The query block holds the whole
GQA group — (G, hd) query rows that share one KV head — so each KV tile is
DMA'd once per group instead of once per query head. Per-sequence valid
lengths arrive via scalar prefetch (``PrefetchScalarGridSpec``): the KV
index maps clamp tiles past ``lengths[b]`` to the last live tile (re-fetch
of a resident block, no dead DMA) and ``pl.when`` predicates their compute
away, so a ragged continuous batch streams only the cache it actually has.
The MLA variant runs in the latent space (k = [latent | k_rope], v = latent)
via the same kernel with K=1, G=H and an explicit softmax scale.

``flash_decode_paged`` is the paged-KV sibling: the cache lives in a
``(num_blocks, K, block_size, hd)`` pool shared by every sequence and each
sequence names its blocks via a ``(B, max_blocks_per_seq)`` int32 block
table. The table rides scalar prefetch next to ``lengths``, so the KV
BlockSpec index maps translate logical tile j -> physical block
``table[b, j]`` before the DMA is issued — same grid, same VMEM carry, same
clamp-and-predicate treatment of tiles past ``lengths[b]`` as the
contiguous kernel, just one extra indirection in the index map.

bf16 accumulation (``REPRO_ATTN_BF16`` / ``lowp=``): dot-product inputs drop
to bf16 — halving the KV bytes the MXU pulls per tile — while online-softmax
statistics and the output accumulator stay f32, matching the chunked path.

Remaining (tracked in ROADMAP.md): dropout, sliding-window masking.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import attn_bf16, divisor_block, resolve_interpret

NEG_INF = -1e30
_LANES = 128  # TPU lane width: m/l scratch and lse/delta rows ride (bq, 128)


def _causal_mask(s, qi, ji, bq, bk, q_offset):
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_offset
    k_idx = ji * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return k_idx <= q_idx


def _grid_params(interpret: bool):
    """dimension_semantics: batch/head/outer-block parallel, inner sequential."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                block_q: int, block_k: int, causal: bool, q_offset: int,
                scale: float, n_kv: int, lowp: bool):
    qi, ji = pl.program_id(2), pl.program_id(3)

    @pl.when(ji == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if causal:
        j_last = jnp.minimum(
            (qi * block_q + block_q - 1 + q_offset) // block_k, n_kv - 1)
        live = ji <= j_last
    else:
        live = ji >= 0  # always true; keeps one code path

    @pl.when(live)
    def _():
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(cdt)
        k = k_ref[0, 0].astype(cdt)
        v = v_ref[0, 0].astype(cdt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_causal_mask(s, qi, ji, block_q, block_k, q_offset),
                          s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ji == n_kv - 1)
    def _():
        m = m_scr[:, :1]
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape[2:])


def _fwd_call(q, k, v, *, causal: bool, q_offset: int, bq: int, bk: int,
              interpret: bool, lowp: bool = False):
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    n_q, n_kv = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)

    def kv_index(b, h, i, j):
        if causal:  # clamp dead above-diagonal tiles to the last live one
            j = jnp.minimum(j, (i * bq + bq - 1 + q_offset) // bk)
        return (b, h, j, 0)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=bq, block_k=bk, causal=causal,
                          q_offset=q_offset, scale=scale, n_kv=n_kv, lowp=lowp),
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **_grid_params(interpret),
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dq (stream KV per query block)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, block_q: int, block_k: int, causal: bool,
               q_offset: int, scale: float, n_kv: int, lowp: bool):
    qi, ji = pl.program_id(2), pl.program_id(3)

    @pl.when(ji == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if causal:
        live = ji <= jnp.minimum(
            (qi * block_q + block_q - 1 + q_offset) // block_k, n_kv - 1)
    else:
        live = ji >= 0

    @pl.when(live)
    def _():
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = q_ref[0, 0].astype(cdt)
        k = k_ref[0, 0].astype(cdt)
        v = v_ref[0, 0].astype(cdt)
        do = do_ref[0, 0].astype(cdt)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(_causal_mask(s, qi, ji, block_q, block_k, q_offset),
                          p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(cdt)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ji == n_kv - 1)
    def _():
        dq_ref[0, 0] = dq_scr[...]


# ---------------------------------------------------------------------------
# backward: dk/dv (stream Q per KV block)
# ---------------------------------------------------------------------------


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, block_q: int, block_k: int,
                causal: bool, q_offset: int, scale: float, n_q: int,
                lowp: bool):
    ji, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # causal: query tiles whose last row is still above this KV tile's first
    # column see none of it — skip them
    live = ((qi + 1) * block_q - 1 + q_offset >= ji * block_k) if causal else qi >= 0

    @pl.when(live)
    def _():
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = q_ref[0, 0].astype(cdt)
        k = k_ref[0, 0].astype(cdt)
        v = v_ref[0, 0].astype(cdt)
        do = do_ref[0, 0].astype(cdt)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(_causal_mask(s, qi, ji, block_q, block_k, q_offset),
                          p, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(cdt), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(cdt)
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_scr[...]
        dv_ref[0, 0] = dv_scr[...]


def _bwd_call(q, k, v, o, lse, do, *, causal: bool, q_offset: int, bq: int,
              bk: int, interpret: bool, lowp: bool = False):
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    n_q, n_kv = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    # Mosaic tiles a block's last two dims by (8, 128): per-row statistics
    # ride lane-broadcast (bq, 128) blocks, as the m/l scratch does
    lanes = (B, H, Sq, _LANES)
    lse = jnp.broadcast_to(lse[..., None], lanes)
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                keepdims=True), lanes)

    def kv_index(b, h, i, j):
        if causal:
            j = jnp.minimum(j, (i * bq + bq - 1 + q_offset) // bk)
        return (b, h, j, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=bq, block_k=bk, causal=causal,
                          q_offset=q_offset, scale=scale, n_kv=n_kv, lowp=lowp),
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
        **_grid_params(interpret),
    )(q, k, v, do, lse, delta)

    def q_index(b, h, j, i):
        if causal:  # clamp dead below-band tiles to the first live one
            i = jnp.maximum(i, (j * bk - q_offset) // bq)
            i = jnp.clip(i, 0, n_q - 1)
        return (b, h, i, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, block_k=bk, causal=causal,
                          q_offset=q_offset, scale=scale, n_q=n_q, lowp=lowp),
        grid=(B, H, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), q_index),
            pl.BlockSpec((1, 1, bq, hd), q_index),
            pl.BlockSpec((1, 1, bq, _LANES), q_index),
            pl.BlockSpec((1, 1, bq, _LANES), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
        **_grid_params(interpret),
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_mha(q, k, v, causal, q_offset, bq, bk, interpret, lowp):
    o, _ = _fwd_call(q, k, v, causal=causal, q_offset=q_offset, bq=bq, bk=bk,
                     interpret=interpret, lowp=lowp)
    return o


def _flash_mha_fwd(q, k, v, causal, q_offset, bq, bk, interpret, lowp):
    o, lse = _fwd_call(q, k, v, causal=causal, q_offset=q_offset, bq=bq, bk=bk,
                       interpret=interpret, lowp=lowp)
    return o, (q, k, v, o, lse[..., 0])


def _flash_mha_bwd(causal, q_offset, bq, bk, interpret, lowp, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, o, lse, do, causal=causal,
                           q_offset=q_offset, bq=bq, bk=bk, interpret=interpret,
                           lowp=lowp)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def flash_attention_mha(q, k, v, *, causal: bool = True, q_offset: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None,
                        lowp: Optional[bool] = None):
    """q,k,v: (B,H,S,hd) same head count. Returns (B,H,Sq,hd); differentiable.

    ``interpret`` and ``lowp`` (bf16 dot inputs, REPRO_ATTN_BF16) resolve
    eagerly here — outside any jit — so env flips take effect per call.
    """
    if q.shape[-1] != k.shape[-1] or q.shape[-1] != v.shape[-1]:
        # MLA prefill has qk_dim != v_dim; the tiled kernel assumes one head
        # dim throughout, so a mismatch silently produces garbage — refuse
        # loudly instead (models.attention.mla_forward falls back to chunked)
        raise ValueError(
            f"flash_attention_mha needs matching q/k/v head dims, got "
            f"q={q.shape[-1]} k={k.shape[-1]} v={v.shape[-1]}; use the "
            f"'chunked' impl for asymmetric-head attention (e.g. MLA prefill)")
    _, _, Sq, _ = q.shape
    Sk = k.shape[2]
    bq = divisor_block(Sq, block_q)
    bk = divisor_block(Sk, block_k)
    return _flash_mha(q, k, v, causal, q_offset, bq, bk,
                      resolve_interpret(interpret), attn_bf16(lowp))


def flash_attention_fwd_lse(q, k, v, *, causal: bool = True, q_offset: int = 0,
                            block_q: int = 128, block_k: int = 128,
                            interpret: Optional[bool] = None,
                            lowp: Optional[bool] = None):
    """Forward that also returns the (B,H,Sq) log-sum-exp residual rows."""
    Sq, Sk = q.shape[2], k.shape[2]
    o, lse = _fwd_call(q, k, v, causal=causal, q_offset=q_offset,
                       bq=divisor_block(Sq, block_q),
                       bk=divisor_block(Sk, block_k),
                       interpret=resolve_interpret(interpret),
                       lowp=attn_bf16(lowp))
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# decode: single query token, per-sequence valid lengths
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, block_k: int, scale: float, n_kv: int, lowp: bool):
    b, ji = pl.program_id(0), pl.program_id(2)

    @pl.when(ji == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(ji * block_k < length)
    def _():
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(cdt)  # (G, hd)
        k = k_ref[0, 0].astype(cdt)                                # (bk, hd)
        v = v_ref[0, 0].astype(cdt)                                # (bk, hdv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kv_idx = ji * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kv_idx < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ji == n_kv - 1)
    def _():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode_grid_params(interpret: bool):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def flash_decode(q, k, v, lengths, *, scale: Optional[float] = None,
                 block_k: int = 256, interpret: Optional[bool] = None,
                 lowp: Optional[bool] = None):
    """Single-query flash decode over a ragged KV cache.

    q: (B, K, G, hd) — one new token's query heads, grouped so the G query
       heads sharing KV head k sit together (GQA: G = H // K; MHA: G = 1).
    k: (B, K, Smax, hd)   v: (B, K, Smax, hdv) — the head-major KV cache
       buffers, so each KV tile is a (bk, hd) slab as Mosaic tiles it.
    lengths: (B,) int32 — row b attends to cache positions < lengths[b];
       rows with length 0 (idle serving slots) produce zeros, not NaNs.

    Grid is (B, K, kv_blocks) with the online-softmax carry in VMEM scratch;
    ``lengths`` rides scalar prefetch so the KV BlockSpec index maps clamp
    tiles past the valid length to the last live tile (no dead-cache DMA) and
    their grid steps are compute-predicated away. Returns (B, K, G, hdv).
    Serving path only: no custom_vjp (decode never backpropagates).
    """
    B, K, G, hd = q.shape
    Smax = k.shape[2]
    hdv = v.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bk = divisor_block(Smax, block_k)
    n_kv = Smax // bk
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    interp = resolve_interpret(interpret)

    def q_index(b, kh, j, len_ref):
        return (b, kh, 0, 0)

    def kv_index(b, kh, j, len_ref):
        # clamp dead tiles past lengths[b] to the last live one: the pipeline
        # re-fetches a resident block instead of DMA-ing cache it won't read
        j = jnp.minimum(j, jnp.maximum(pl.cdiv(len_ref[b], bk) - 1, 0))
        return (b, kh, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), q_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hdv), q_index),
        scratch_shapes=[
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, hdv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, scale=scale, n_kv=n_kv,
                          lowp=attn_bf16(lowp)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hdv), q.dtype),
        interpret=interp,
        name="flash_decode",
        **_decode_grid_params(interp),
    )(lengths, q, k, v)


# ---------------------------------------------------------------------------
# paged decode: KV in a block pool, indexed through a block table
# ---------------------------------------------------------------------------


def _decode_paged_kernel(len_ref, tbl_ref, *rest, **kw):
    # the block table is consumed entirely by the BlockSpec index maps; the
    # kernel body is the contiguous single-query kernel unchanged (online
    # softmax over tiles, compute predicated past lengths[b])
    del tbl_ref
    _decode_kernel(len_ref, *rest, **kw)


def flash_decode_paged(q, k_pool, v_pool, block_table, lengths, *,
                       scale: Optional[float] = None,
                       interpret: Optional[bool] = None,
                       lowp: Optional[bool] = None):
    """Single-query flash decode over a paged (block-pooled) KV cache.

    q: (B, K, G, hd) — grouped query heads, as in ``flash_decode``.
    k_pool: (num_blocks, K, block_size, hd)  v_pool: (..., hdv) — physical
       KV blocks shared by all sequences (no batch dimension), head-major
       inside a block.
    block_table: (B, T) int32 — logical block j of sequence b lives in
       physical block ``block_table[b, j]``; rows may point unused tail
       entries at any valid block (they are clamped and predicated away).
    lengths: (B,) int32 — row b attends to virtual positions < lengths[b]
       (position p lives at offset p % block_size of logical block
       p // block_size); rows with length 0 produce zeros.

    Grid is (B, K, T): the kernel tile IS the pool block, so each grid step
    DMAs exactly one physical block, located by the scalar-prefetched table.
    Tiles past ``lengths[b]`` clamp to the last live logical block (re-fetch
    of a resident physical block, no dead DMA) and their compute is
    predicated away — identical math to ``flash_decode`` on the contiguous
    cache the table describes. Returns (B, K, G, hdv).
    """
    B, K, G, hd = q.shape
    num_blocks, bs = k_pool.shape[0], k_pool.shape[2]
    hdv = v_pool.shape[-1]
    T = block_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    table = jnp.clip(jnp.asarray(block_table, jnp.int32), 0, num_blocks - 1)
    interp = resolve_interpret(interpret)

    def q_index(b, kh, j, len_ref, tbl_ref):
        return (b, kh, 0, 0)

    def kv_index(b, kh, j, len_ref, tbl_ref):
        # clamp dead tiles past lengths[b] to the last live logical block,
        # then translate logical -> physical through the block table
        j = jnp.minimum(j, jnp.maximum(pl.cdiv(len_ref[b], bs) - 1, 0))
        return (tbl_ref[b, j], kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, T),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), q_index),
            pl.BlockSpec((1, 1, bs, hd), kv_index),
            pl.BlockSpec((1, 1, bs, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hdv), q_index),
        scratch_shapes=[
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, hdv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_paged_kernel, block_k=bs, scale=scale,
                          n_kv=T, lowp=attn_bf16(lowp)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hdv), q.dtype),
        interpret=interp,
        name="flash_decode_paged",
        **_decode_grid_params(interp),
    )(lengths, table, q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# speculative decode: q-block of draft positions, causal masking in the tile
# ---------------------------------------------------------------------------


def _spec_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                        acc_scr, *, block_k: int, scale: float, n_kv: int,
                        n_draft: int, group: int, lowp: bool):
    """The single-query decode kernel grown to a q-block of draft positions.

    The q block holds the S = n_draft draft positions of one (batch, KV
    head) program, flattened (by the wrapper, outside the kernel) together
    with the G-row query group to (S*G, hd) rows where row r = qi*G + g.
    ``len_ref[b]`` is the BASE cache length — the valid count *before* the
    draft KVs were scattered at positions base..base+S-1 — so draft position
    qi attends cache positions < base + qi + 1: the per-row causal mask
    lives inside the tile, and the online-softmax carry is per row exactly
    as in ``_decode_kernel``.
    """
    b, ji = pl.program_id(0), pl.program_id(2)

    @pl.when(ji == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    base = len_ref[b]

    @pl.when(ji * block_k < base + n_draft)
    def _():
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(cdt)  # (S*G, hd)
        k = k_ref[0, 0].astype(cdt)                                # (bk, hd)
        v = v_ref[0, 0].astype(cdt)                                # (bk, hdv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kv_idx = ji * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(kv_idx < base + qi + 1, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ji == n_kv - 1)
    def _():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_decode_spec(q, k, v, lengths, *, scale: Optional[float] = None,
                      block_k: int = 256, interpret: Optional[bool] = None,
                      lowp: Optional[bool] = None):
    """Multi-token speculative verify over a ragged contiguous KV cache.

    q: (B, K, S, G, hd) — S draft positions' query heads, grouped per KV
       head as in ``flash_decode`` (GQA: G = H // K).
    k: (B, K, Smax, hd)   v: (B, K, Smax, hdv) — head-major cache buffers
       with the S draft tokens' KV already scattered at positions
       lengths[b]..lengths[b]+S-1.
    lengths: (B,) int32 — BASE valid counts (before the drafts); draft
       position qi of row b attends cache positions < lengths[b] + qi + 1.

    One grid step per KV tile verifies all S positions at once: same
    (B, K, kv_blocks) grid, scalar-prefetch clamp, and predication as the
    single-query kernel, with the causal mask applied per q-row inside the
    tile. Returns (B, K, S, G, hdv). Serving path only (no custom_vjp).
    """
    B, K, S, G, hd = q.shape
    Smax = k.shape[2]
    hdv = v.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bk = divisor_block(Smax, block_k)
    n_kv = Smax // bk
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    interp = resolve_interpret(interpret)

    def q_index(b, kh, j, len_ref):
        return (b, kh, 0, 0)

    def kv_index(b, kh, j, len_ref):
        # the last live tile now covers the drafts too: clamp at base + S
        j = jnp.minimum(j, jnp.maximum(pl.cdiv(len_ref[b] + S, bk) - 1, 0))
        return (b, kh, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, S * G, hd), q_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, S * G, hdv), q_index),
        scratch_shapes=[
            pltpu.VMEM((S * G, _LANES), jnp.float32),
            pltpu.VMEM((S * G, _LANES), jnp.float32),
            pltpu.VMEM((S * G, hdv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_spec_decode_kernel, block_k=bk, scale=scale,
                          n_kv=n_kv, n_draft=S, group=G, lowp=attn_bf16(lowp)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, S * G, hdv), q.dtype),
        interpret=interp,
        name="flash_decode_spec",
        **_decode_grid_params(interp),
    )(lengths, q.reshape(B, K, S * G, hd), k, v)
    return out.reshape(B, K, S, G, hdv)


def _spec_decode_paged_kernel(len_ref, tbl_ref, *rest, **kw):
    # as in the single-query paged kernel, the table is consumed entirely by
    # the BlockSpec index maps; the body is the contiguous spec kernel
    del tbl_ref
    _spec_decode_kernel(len_ref, *rest, **kw)


def flash_decode_spec_paged(q, k_pool, v_pool, block_table, lengths, *,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            lowp: Optional[bool] = None):
    """Multi-token speculative verify over a paged (block-pooled) KV cache.

    q: (B, K, S, G, hd) — S draft positions, grouped as in
       ``flash_decode_spec``. k_pool/v_pool: (num_blocks, K, block_size, .)
       physical blocks; block_table: (B, T) int32 with blocks mapped through
       position lengths[b] + S - 1 (the engine appends draft positions before
       the verify call, so boundary blocks already exist).
    lengths: (B,) int32 BASE valid counts, as in ``flash_decode_spec``.

    Grid is (B, K, T) with the tile = pool block; dead tiles clamp to the
    last logical block covering base + S. Returns (B, K, S, G, hdv).
    """
    B, K, S, G, hd = q.shape
    num_blocks, bs = k_pool.shape[0], k_pool.shape[2]
    hdv = v_pool.shape[-1]
    T = block_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    table = jnp.clip(jnp.asarray(block_table, jnp.int32), 0, num_blocks - 1)
    interp = resolve_interpret(interpret)

    def q_index(b, kh, j, len_ref, tbl_ref):
        return (b, kh, 0, 0)

    def kv_index(b, kh, j, len_ref, tbl_ref):
        j = jnp.minimum(j, jnp.maximum(pl.cdiv(len_ref[b] + S, bs) - 1, 0))
        return (tbl_ref[b, j], kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, T),
        in_specs=[
            pl.BlockSpec((1, 1, S * G, hd), q_index),
            pl.BlockSpec((1, 1, bs, hd), kv_index),
            pl.BlockSpec((1, 1, bs, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, S * G, hdv), q_index),
        scratch_shapes=[
            pltpu.VMEM((S * G, _LANES), jnp.float32),
            pltpu.VMEM((S * G, _LANES), jnp.float32),
            pltpu.VMEM((S * G, hdv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_spec_decode_paged_kernel, block_k=bs, scale=scale,
                          n_kv=T, n_draft=S, group=G, lowp=attn_bf16(lowp)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, S * G, hdv), q.dtype),
        interpret=interp,
        name="flash_decode_spec_paged",
        **_decode_grid_params(interp),
    )(lengths, table, q.reshape(B, K, S * G, hd), k_pool, v_pool)
    return out.reshape(B, K, S, G, hdv)
