"""Fused RMSNorm Pallas kernels: forward + custom_vjp backward.

Tiling: rows in the sublane dim, the full feature dim in lanes. One grid step
normalizes a (block_rows, d) tile entirely in VMEM — a single HBM read and
write per element (XLA's unfused version reads x twice: once for the moment,
once for the scale-multiply).

The backward is fused the same way. Residuals are just (x, scale): the
rsqrt moment is recomputed in-tile (cheaper than a second HBM stream for a
saved rstd). With ``r = rsqrt(mean(x^2)+eps)`` and ``gs = g*scale``:

  dx     = r*gs - x * r^3 * mean(gs*x, -1)
  dscale = sum_rows(g * x * r)

``dscale`` needs a cross-tile reduction, so the kernel emits per-tile
partials of shape (n_tiles, 1, d) and the wrapper sums them — an
O(n_tiles*d) tensor, not O(rows*d). The singleton middle dim makes each
partial a (1, d) block equal to its array's last two dims, which Mosaic's
(8, 128) block tiling accepts where a (1, d) row of (n_tiles, d) is not.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import divisor_block, resolve_interpret


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_bwd_kernel(x_ref, scale_ref, g_ref, dx_ref, dsp_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    s = scale_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    gs = g * s
    dot = jnp.mean(gs * x, axis=-1, keepdims=True)
    dx_ref[...] = (r * gs - x * (r * r * r) * dot).astype(dx_ref.dtype)
    dsp_ref[0] = jnp.sum(g * x * r, axis=0, keepdims=True)


def _fwd_call(x2, scale, *, eps: float, br: int, interpret: bool):
    rows, d = x2.shape
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
        name="rmsnorm_fwd",
    )(x2, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm2d(x2, scale, eps, br, interpret):
    return _fwd_call(x2, scale, eps=eps, br=br, interpret=interpret)


def _rmsnorm2d_fwd(x2, scale, eps, br, interpret):
    return _fwd_call(x2, scale, eps=eps, br=br, interpret=interpret), (x2, scale)


def _rmsnorm2d_bwd(eps, br, interpret, res, g):
    x2, scale = res
    rows, d = x2.shape
    n_blocks = rows // br
    dx, dsp = pl.pallas_call(
        functools.partial(_rmsnorm_bwd_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x2.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, d), jnp.float32),
        ],
        interpret=interpret,
        name="rmsnorm_bwd",
    )(x2, scale, g)
    return dx, dsp.sum((0, 1)).astype(scale.dtype)


_rmsnorm2d.defvjp(_rmsnorm2d_fwd, _rmsnorm2d_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: Optional[bool] = None):
    """x: (..., d); scale: (d,). Differentiable (custom_vjp backward kernel)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    out = _rmsnorm2d(x2, scale, eps, divisor_block(rows, block_rows),
                     resolve_interpret(interpret))
    return out.reshape(orig_shape)
