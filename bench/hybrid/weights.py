"""Seeded weights of the layer-pattern hybrid, made on the device in one
jitted call.

The layout is the benchmark's own: a flat dict, each run of consecutive
layers of one kind stacked along a leading axis under ``runs.<i>.``, in
layer order. Mamba2's ``A_log`` and ``dt_bias`` follow the published
initialisation (A uniform in [1, 16], dt log-uniform in [0.001, 0.1]), so
the decays are those of a model about to be fine-tuned.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.hybrid import Dims
from bench.weights import seed_key

DT_MIN, DT_MAX = 1e-3, 1e-1


def shapes(m: Dims) -> Dict[str, Tuple[int, ...]]:
    out = {"embed": (m.vocab, m.d), "final_norm.scale": (m.d,)}
    for i, (kind, n) in enumerate(m.runs()):
        r = f"runs.{i}."
        out.update({r + "in_norm.scale": (n, m.d), r + "mlp_norm.scale": (n, m.d),
                    r + "w_gate": (n, m.d, m.ff), r + "w_up": (n, m.d, m.ff),
                    r + "w_down": (n, m.ff, m.d)})
        if kind == "mamba":
            nh = m.ssm_heads
            out.update({r + "in_proj": (n, m.d, m.in_proj),
                        r + "conv.weight": (n, m.conv_width, m.conv_channels),
                        r + "conv.bias": (n, m.conv_channels),
                        r + "dt_bias": (n, nh), r + "A_log": (n, nh), r + "D": (n, nh),
                        r + "gate_norm.scale": (n, m.d_inner),
                        r + "out_proj": (n, m.d_inner, m.d)})
        else:
            q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
            out.update({r + "wq": (n, m.d, q), r + "wk": (n, m.d, kv),
                        r + "wv": (n, m.d, kv), r + "wo": (n, q, m.d)})
    return out


def _leaf(key, name: str, shape, dtype):
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("dt_bias", "A_log"):
        u = jax.random.uniform(key, shape, jnp.float32)
        if leaf == "A_log":
            return jnp.log(1.0 + 15.0 * u).astype(dtype)
        dt = jnp.exp(math.log(DT_MIN) + u * (math.log(DT_MAX) - math.log(DT_MIN)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1(dt)
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        v = 0.02 * z
    elif leaf in ("scale", "D"):
        v = 1.0 + 0.1 * z
    elif leaf == "bias":
        v = 0.1 * z
    else:  # a matrix (..., fan_in, fan_out) or the conv (..., width, channels)
        v = z / math.sqrt(shape[-2])
    return v.astype(dtype)


def make(m: Dims, seed: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Every weight of ``m`` from ``seed``, on the default device."""
    names = sorted(shapes(m).items())

    def build(key):
        return {n: _leaf(jax.random.fold_in(key, i), n, shp, dtype)
                for i, (n, shp) in enumerate(names)}

    return jax.jit(build)(seed_key(seed))
