"""Flash-attention Pallas kernels: fused forward + custom_vjp backward.

This is the training-grade attention hot path (``impl="pallas"``). Three
kernels share one grid plan (``_train_plan``):

  forward   grid (B, H/hb, live q-major tiles)    -> o, lse
  dq        grid (B, H/hb, live q-major tiles)    -> dq
  dkv       grid (B, H/hb, live kv-major tiles)   -> dk, dv

Tiling / residual layout
------------------------
Each grid step works one (q block, kv block) tile of ``hb`` heads at once:
blocks are (1, hb, block_q, hd) and (1, hb, block_k, hd), and the in-kernel
dots carry the head block as a batch dimension. The plan derives
``block_q``, ``block_k`` and ``hb`` from the shapes: the largest sequence
blocks up to ``_SEQ_BLOCK`` (through ``divisor_block``, so ragged lengths
take the largest divisor), then the largest head block dividing H whose
(hb, block_q, block_k) f32 score tile stays within ``_SCORE_ELEMS`` and
whose VMEM estimate (double-buffered tiles at their 128-lane padding,
scratch, the f32 score temporaries) stays within ``_VMEM_BUDGET``. A plan
that needs more than the default scoped VMEM sets ``vmem_limit_bytes``.
Explicit ``block_q`` / ``block_k`` arguments are honoured.

The last grid axis runs sequentially ("arbitrary"), so the online-softmax
state — running max ``m``, normalizer ``l`` and the output accumulator, all
(hb, block_q, .) — lives in VMEM scratch that carries across the KV tiles of
one query block, and the (Sq, Sk) score matrix never exists anywhere. The
forward additionally emits the log-sum-exp rows ``lse = m + log(l)``; the
backward keeps them as (B, H, Sq) and, with the saved output ``o``, that is
the entire residual, O(B*H*Sq) instead of the O(Sq*Sk) probability matrix.
Mosaic tiles a block's last two dims by (8, 128), so inside the kernels the
per-row statistics (lse, and the backward's ``delta``) travel
lane-broadcast as (B, H, Sq, 128).

Causal block skipping
---------------------
The last grid axis enumerates only live tiles. ``_step_table`` lists, per
output block, the tiles that touch it — for the forward and dq each query
block's KV blocks from 0 to the diagonal, for dkv each KV block's query
blocks from the diagonal down — and the three tables (q block, kv block,
flags) ride scalar prefetch (``PrefetchScalarGridSpec``), so the BlockSpec
index maps DMA exactly the tiles the table names and no grid step is
predicated out. The flags mark a block's first tile (reset the carry), its
last (write the output), and whether the tile straddles the diagonal: only
those run the causal mask (iota, compare, select); tiles wholly below it run
unmasked. An output block that no tile reaches (dkv's KV blocks past the
last query, under causal) gets one dead step that writes zeros.

Backward derivation (AttentionEngine-style online recomputation): with
``p = exp(s - lse)`` and ``delta = rowsum(do * o)``,

  dv = p^T @ do
  ds = p * (do @ v^T - delta)
  dq = scale * ds @ k          (accumulated over KV tiles)
  dk = scale * ds^T @ q        (accumulated over Q tiles)

``jax.custom_vjp`` wires these in so ``jax.grad`` through ``impl="pallas"``
never differentiates the Pallas forward. Heads are pre-broadcast by the
wrapper (GQA handled in ops.py, whose broadcast transpose sums dk/dv over the
query-head group). The wrapper sets the ``repro.obs`` gauge
``flash_grid_steps{kernel=fwd|dq|dkv, live=1|0}``: each call's live and dead
grid steps, as planned when it traces.

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

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.backend import attn_bf16, divisor_block, resolve_interpret

NEG_INF = -1e30
_LANES = 128  # TPU lane width: m/l scratch and lse/delta rows ride (bq, 128)

# grid plan of the training kernels. On a TPU v5e at 2048 positions, 1024 x
# 1024 tiles of one head beat 512 x 512 of one to four heads and 2048 x 2048
# (fixed cost per step and per query row against the causal waste of the
# diagonal tiles), at head_dim 64 and 128 alike
_SEQ_BLOCK = 1024            # preferred block_q / block_k
_SCORE_ELEMS = 1024 * 1024   # most (hb, block_q, block_k) score elements a step
_VMEM_BUDGET = 48 << 20      # most VMEM a plan may estimate for one kernel
_SCOPED_VMEM = 16 << 20      # Mosaic's default scoped VMEM limit (TPU v5e)

_KERNELS = ("fwd", "dq", "dkv")

# step-table flags: a block's first and last tile; a live tile that needs no
# mask (FULL) or straddles the causal diagonal (MASK)
_FIRST, _LAST, _FULL, _MASK = 1, 2, 4, 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.lru_cache(maxsize=64)
def _step_table(n_q: int, n_kv: int, bq: int, bk: int, q_offset: int,
                causal: bool, kv_major: bool) -> np.ndarray:
    """The grid steps of one (batch, head block): a (3, steps) int32 table of
    q block, kv block and flags, grouped by output block (query blocks for
    the forward and dq, ``kv_major`` KV blocks for dkv), live tiles only."""
    rows = []
    for o in range(n_kv if kv_major else n_q):
        block = []
        for i in range(n_q if kv_major else n_kv):
            qi, ki = (i, o) if kv_major else (o, i)
            q_lo = qi * bq + q_offset
            if not causal or ki * bk + bk - 1 <= q_lo:
                block.append([qi, ki, _FULL])
            elif ki * bk <= q_lo + bq - 1:
                block.append([qi, ki, _MASK])
        if not block:  # no tile reaches this output block: zero-fill it
            block.append([n_q - 1, o, 0] if kv_major else [o, 0, 0])
        block[0][2] |= _FIRST
        block[-1][2] |= _LAST
        rows += block
    table = np.asarray(rows, np.int32).T
    table.setflags(write=False)  # shared by every caller of the cache
    return table


@dataclasses.dataclass(frozen=True)
class _Plan:
    """Grid plan of one flash attention call's three training kernels."""
    B: int
    H: int
    Sq: int
    Sk: int
    hd: int
    q_offset: int
    causal: bool
    itemsize: int
    bq: int
    bk: int
    hb: int

    def table(self, kv_major: bool) -> np.ndarray:
        return _step_table(self.Sq // self.bq, self.Sk // self.bk, self.bq,
                           self.bk, self.q_offset, self.causal, kv_major)

    def grid(self, kv_major: bool):
        return (self.B, self.H // self.hb, self.table(kv_major).shape[1])

    def grid_steps(self, kernel: str):
        """(total, live) grid steps of one call of ``kernel``."""
        flags = self.table(kernel == "dkv")[2]
        per = self.B * (self.H // self.hb)
        return per * flags.size, per * int(np.count_nonzero(
            flags & (_FULL | _MASK)))

    def vmem_bytes(self, kernel: str) -> int:
        """VMEM estimate of one kernel: double-buffered blocks at their
        (8, 128) padding, scratch, and the f32 (hb, bq, bk) temporaries
        (score, probability, mask; the backward's dp and ds)."""
        hb, hd = self.hb, _round_up(self.hd, _LANES)
        q_tile = hb * _round_up(self.bq, 8) * hd
        k_tile = hb * _round_up(self.bk, 8) * hd
        rows = hb * _round_up(self.bq, 8) * _LANES * 4
        score = hb * _round_up(self.bq, 8) * _round_up(self.bk, _LANES) * 4
        x = self.itemsize
        if kernel == "fwd":  # q, k, v in; o, lse out; m, l, acc scratch
            io, scratch, temps = (2 * q_tile + 2 * k_tile) * x + rows, \
                2 * rows + 4 * q_tile, 3
        elif kernel == "dq":  # q, k, v, do, lse, delta in; dq out; acc
            io, scratch, temps = (2 * q_tile + 2 * k_tile) * x + 2 * rows \
                + 4 * q_tile, 4 * q_tile, 4
        else:  # dkv: k, v, q, do, lse, delta in; dk, dv out; two accs
            io, scratch, temps = (2 * q_tile + 2 * k_tile) * x + 2 * rows \
                + 8 * k_tile, 8 * k_tile, 4
        return 2 * io + scratch + temps * score

    @property
    def vmem_limit(self) -> Optional[int]:
        """``vmem_limit_bytes`` for the kernels: None where the default
        scoped limit holds the largest estimate, else that estimate and a
        quarter more, rounded up to a whole MiB."""
        need = max(self.vmem_bytes(k) for k in _KERNELS)
        if need <= _SCOPED_VMEM:
            return None
        return _round_up(need + need // 4, 1 << 20)


def _train_plan(B: int, H: int, Sq: int, Sk: int, hd: int, q_offset: int,
                causal: bool, *, block_q: Optional[int] = None,
                block_k: Optional[int] = None, itemsize: int = 4) -> _Plan:
    """Blocks and heads per grid step for the shapes: the sequence blocks
    as given, else the largest divisors up to ``_SEQ_BLOCK``; then the
    largest head block dividing H within the score-tile and VMEM budgets."""
    bq = divisor_block(Sq, block_q or _SEQ_BLOCK)
    bk = divisor_block(Sk, block_k or _SEQ_BLOCK)
    plans = [_Plan(B, H, Sq, Sk, hd, q_offset, causal, itemsize, bq, bk, hb)
             for hb in range(1, H + 1) if H % hb == 0]
    return [p for p in plans if p.hb == 1 or (
        p.hb * bq * bk <= _SCORE_ELEMS
        and max(p.vmem_bytes(k) for k in _KERNELS) <= _VMEM_BUDGET)][-1]


def _publish_steps(plan: _Plan, kernels) -> None:
    """Set the ``flash_grid_steps`` gauge: live and dead grid steps of one
    call of each kernel under ``plan``."""
    gauge = obs.get_telemetry().metrics.gauge(
        "flash_grid_steps", "grid steps of one flash kernel call")
    for kernel in kernels:
        total, live = plan.grid_steps(kernel)
        gauge.labels(kernel=kernel, live=1).set(live)
        gauge.labels(kernel=kernel, live=0).set(total - live)


def _causal_mask(shape, qi, ki, plan: _Plan):
    """(hb, bq, bk) mask of the tile (qi, ki): key position <= query's."""
    q_idx = qi * plan.bq + plan.q_offset + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)
    k_idx = ki * plan.bk + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    return k_idx <= q_idx


def _bdot(a, b, ca: int, cb: int):
    """Head-batched dot: contract ``a``'s dim ``ca`` with ``b``'s ``cb``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _live(flags, causal: bool, tile) -> None:
    """Run ``tile(masked)`` on a live step, masking only a tile that
    straddles the causal diagonal."""
    pl.when(flags & _FULL != 0)(lambda: tile(False))
    if causal:
        pl.when(flags & _MASK != 0)(lambda: tile(True))


def _train_call(kernel, plan: _Plan, kv_major: bool, operands, in_specs,
                out_specs, out_shape, scratch_shapes, interpret: bool,
                name: str):
    """A training kernel's ``pallas_call`` over the plan's live tiles: the
    step table's three rows ride scalar prefetch ahead of the operands."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=plan.grid(kv_major), in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit)}
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret, name=name, **params)(
        *(jnp.asarray(row) for row in plan.table(kv_major)), *operands)


def _q_spec(plan: _Plan, lanes: Optional[int] = None):
    """(1, hb, bq, .) blocks indexed by the step's query block."""
    return pl.BlockSpec((1, plan.hb, plan.bq, lanes or plan.hd),
                        lambda b, h, t, qt, kt, ft: (b, h, qt[t], 0))


def _kv_spec(plan: _Plan):
    """(1, hb, bk, hd) blocks indexed by the step's KV block."""
    return pl.BlockSpec((1, plan.hb, plan.bk, plan.hd),
                        lambda b, h, t, qt, kt, ft: (b, h, kt[t], 0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(qt_ref, kt_ref, ft_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, plan: _Plan, scale: float,
                lowp: bool):
    t = pl.program_id(2)
    flags = ft_ref[t]

    @pl.when(flags & _FIRST != 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked):
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = (q_ref[0].astype(jnp.float32) * scale).astype(cdt)
        k = k_ref[0].astype(cdt)
        v = v_ref[0].astype(cdt)
        s = _bdot(q, k, 2, 2)
        if masked:
            s = jnp.where(_causal_mask(s.shape, qt_ref[t], kt_ref[t], plan),
                          s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + _bdot(p.astype(cdt), v, 2, 1)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    _live(flags, plan.causal, tile)

    @pl.when(flags & _LAST != 0)
    def _():
        m = m_scr[:, :, :1]
        l = jnp.maximum(l_scr[:, :, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape[1:])


def _fwd_call(q, k, v, *, plan: _Plan, scale: float, interpret: bool,
              lowp: bool = False):
    B, H, Sq, hd = q.shape
    rows = (plan.hb, plan.bq, _LANES)
    return _train_call(
        functools.partial(_fwd_kernel, plan=plan, scale=scale, lowp=lowp),
        plan, False, (q, k, v),
        in_specs=[_q_spec(plan), _kv_spec(plan), _kv_spec(plan)],
        out_specs=[_q_spec(plan), _q_spec(plan, _LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM(rows, jnp.float32),
            pltpu.VMEM(rows, jnp.float32),
            pltpu.VMEM((plan.hb, plan.bq, hd), jnp.float32),
        ],
        interpret=interpret, name="flash_fwd")


# ---------------------------------------------------------------------------
# backward: dq (stream KV per query block)
# ---------------------------------------------------------------------------


def _dq_kernel(qt_ref, kt_ref, ft_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, plan: _Plan, scale: float,
               lowp: bool):
    t = pl.program_id(2)
    flags = ft_ref[t]

    @pl.when(flags & _FIRST != 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(masked):
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = q_ref[0].astype(cdt)
        k = k_ref[0].astype(cdt)
        v = v_ref[0].astype(cdt)
        do = do_ref[0].astype(cdt)
        lse = lse_ref[0][:, :, :1]
        delta = delta_ref[0][:, :, :1]
        s = scale * _bdot(q, k, 2, 2)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(_causal_mask(s.shape, qt_ref[t], kt_ref[t], plan),
                          p, 0.0)
        dp = _bdot(do, v, 2, 2)
        ds = (p * (dp - delta)).astype(cdt)
        dq_scr[...] += scale * _bdot(ds, k, 2, 1)

    _live(flags, plan.causal, tile)

    @pl.when(flags & _LAST != 0)
    def _():
        dq_ref[0] = dq_scr[...]


# ---------------------------------------------------------------------------
# backward: dk/dv (stream Q per KV block)
# ---------------------------------------------------------------------------


def _dkv_kernel(qt_ref, kt_ref, ft_ref, k_ref, v_ref, q_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, plan: _Plan,
                scale: float, lowp: bool):
    t = pl.program_id(2)
    flags = ft_ref[t]

    @pl.when(flags & _FIRST != 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(masked):
        cdt = jnp.bfloat16 if lowp else jnp.float32
        q = q_ref[0].astype(cdt)
        k = k_ref[0].astype(cdt)
        v = v_ref[0].astype(cdt)
        do = do_ref[0].astype(cdt)
        lse = lse_ref[0][:, :, :1]
        delta = delta_ref[0][:, :, :1]
        s = scale * _bdot(q, k, 2, 2)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(_causal_mask(s.shape, qt_ref[t], kt_ref[t], plan),
                          p, 0.0)
        dv_scr[...] += _bdot(p.astype(cdt), do, 1, 1)
        dp = _bdot(do, v, 2, 2)
        ds = (p * (dp - delta)).astype(cdt)
        dk_scr[...] += scale * _bdot(ds, q, 1, 1)

    _live(flags, plan.causal, tile)

    @pl.when(flags & _LAST != 0)
    def _():
        dk_ref[0] = dk_scr[...]
        dv_ref[0] = dv_scr[...]


def _bwd_call(q, k, v, o, lse, do, *, plan: _Plan, scale: float,
              interpret: bool, lowp: bool = False):
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    # Mosaic tiles a block's last two dims by (8, 128): per-row statistics
    # ride lane-broadcast (bq, 128) blocks, as the m/l scratch does
    lanes = (B, H, Sq, _LANES)
    lse = jnp.broadcast_to(lse[..., None], lanes)
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                keepdims=True), lanes)
    row_spec = _q_spec(plan, _LANES)

    dq = _train_call(
        functools.partial(_dq_kernel, plan=plan, scale=scale, lowp=lowp),
        plan, False, (q, k, v, do, lse, delta),
        in_specs=[_q_spec(plan), _kv_spec(plan), _kv_spec(plan),
                  _q_spec(plan), row_spec, row_spec],
        out_specs=_q_spec(plan),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((plan.hb, plan.bq, hd), jnp.float32)],
        interpret=interpret, name="flash_dq")

    acc = pltpu.VMEM((plan.hb, plan.bk, hd), jnp.float32)
    dk, dv = _train_call(
        functools.partial(_dkv_kernel, plan=plan, scale=scale, lowp=lowp),
        plan, True, (k, v, q, do, lse, delta),
        in_specs=[_kv_spec(plan), _kv_spec(plan), _q_spec(plan),
                  _q_spec(plan), row_spec, row_spec],
        out_specs=[_kv_spec(plan), _kv_spec(plan)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
        ],
        scratch_shapes=[acc, acc],
        interpret=interpret, name="flash_dkv")
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_mha(q, k, v, plan, scale, interpret, lowp):
    o, _ = _fwd_call(q, k, v, plan=plan, scale=scale, interpret=interpret,
                     lowp=lowp)
    return o


def _flash_mha_fwd(q, k, v, plan, scale, interpret, lowp):
    o, lse = _fwd_call(q, k, v, plan=plan, scale=scale, interpret=interpret,
                       lowp=lowp)
    return o, (q, k, v, o, lse[..., 0])


def _flash_mha_bwd(plan, scale, interpret, lowp, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, o, lse, do, plan=plan, scale=scale,
                           interpret=interpret, lowp=lowp)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def _scale(scale: Optional[float], q) -> float:
    """The softmax scale: as given, else ``1/sqrt(head_dim)``."""
    return 1.0 / math.sqrt(q.shape[-1]) if scale is None else float(scale)


def _plan_for(q, k, causal, q_offset, block_q, block_k) -> _Plan:
    B, H, Sq, hd = q.shape
    return _train_plan(B, H, Sq, k.shape[2], hd, q_offset, causal,
                       block_q=block_q, block_k=block_k,
                       itemsize=q.dtype.itemsize)


def flash_attention_mha(q, k, v, *, causal: bool = True, q_offset: int = 0,
                        scale: Optional[float] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        lowp: Optional[bool] = None):
    """q,k,v: (B,H,S,hd) same head count. Returns (B,H,Sq,hd); differentiable.

    ``scale`` is the softmax scale (default ``1/sqrt(hd)``).
    ``block_q`` / ``block_k`` default to the grid plan's (``_train_plan``).
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
    plan = _plan_for(q, k, causal, q_offset, block_q, block_k)
    _publish_steps(plan, _KERNELS)
    return _flash_mha(q, k, v, plan, _scale(scale, q),
                      resolve_interpret(interpret), attn_bf16(lowp))


def flash_attention_fwd_lse(q, k, v, *, causal: bool = True, q_offset: int = 0,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            lowp: Optional[bool] = None):
    """Forward that also returns the (B,H,Sq) log-sum-exp residual rows."""
    plan = _plan_for(q, k, causal, q_offset, block_q, block_k)
    _publish_steps(plan, ("fwd",))
    o, lse = _fwd_call(q, k, v, plan=plan, scale=_scale(None, q),
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
