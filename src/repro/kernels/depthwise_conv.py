"""Depthwise (kh x kw) conv Pallas kernel — the paper's hot op.

Swan's §3.1 observation: depthwise conv is memory-bound, and on ARM CPUs
multi-core execution cache-thrashes. The TPU-native adaptation (DESIGN.md §2)
is to tile for the HBM->VMEM->VREG hierarchy instead of the GPU refactoring
trick the paper cites [42]: channels ride the 128-wide lane dim (depthwise is
elementwise-in-channel, so lanes never interact), a (batch, channel-block)
grid keeps each tile's working set resident in VMEM, and the kh*kw taps
become shifted multiply-accumulates over the resident tile — exactly one HBM
read and one HBM write per element, the memory-roofline optimum. No
cross-tile traffic, hence nothing to thrash.

Stride 1, SAME padding (the shape inside MobileNet/ShuffleNet residual units).
Rows are pre-padded outside the kernel so all tap slices are static.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import divisor_block, resolve_interpret


def _dw_kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, H: int, W: int):
    """x block: (1, H+kh-1, W, Cb) row-padded; w: (kh,kw,Cb); out: (1,H,W,Cb)."""
    x = x_ref[0].astype(jnp.float32)  # (H+kh-1, W, Cb)
    cb = x.shape[-1]
    acc = jnp.zeros((H, W, cb), jnp.float32)
    pw = (kw - 1) // 2
    for a in range(kh):
        rows = jax.lax.slice_in_dim(x, a, a + H, axis=0)  # (H, W, Cb)
        for c in range(kw):
            tap = w_ref[a, c, :].astype(jnp.float32)
            ox = c - pw
            lo, hi = max(0, -ox), W - max(0, ox)
            if hi <= lo:
                continue
            src = jax.lax.slice_in_dim(rows, lo + ox, hi + ox, axis=1) * tap
            contrib = jnp.zeros((H, W, cb), jnp.float32)
            contrib = jax.lax.dynamic_update_slice_in_dim(contrib, src, lo, axis=1)
            acc = acc + contrib
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("channel_block", "interpret"))
def depthwise_conv(x, w, *, channel_block: int = 128,
                   interpret: Optional[bool] = None):
    """x: (B,H,W,C); w: (kh,kw,C); stride 1, SAME padding, odd kernel dims."""
    interpret = resolve_interpret(interpret)
    B, H, W, C = x.shape
    kh, kw = w.shape[0], w.shape[1]
    ph = (kh - 1) // 2
    cb = divisor_block(C, channel_block)
    xp = jnp.pad(x, ((0, 0), (ph, ph), (0, 0), (0, 0)))
    grid = (B, C // cb)
    return pl.pallas_call(
        functools.partial(_dw_kernel, kh=kh, kw=kw, H=H, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H + kh - 1, W, cb), lambda b, c: (b, 0, 0, c)),
            pl.BlockSpec((kh, kw, cb), lambda b, c: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, H, W, cb), lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), x.dtype),
        interpret=interpret,
        name="depthwise_conv",
    )(xp, w)
