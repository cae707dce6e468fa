"""Seeded weights of a dense decoder, made on the device in one jitted call.

The layout is the benchmark's own: a flat dict of stacked per-layer arrays
(leading axis = layer). Drivers hand the same arrays to the program in its
own tree; the reference reads them by these names.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import Spec


def shapes(s: Spec) -> Dict[str, Tuple[int, ...]]:
    L, d, hd = s.layers, s.d, s.head_dim
    out = {
        "embed": (s.vocab, d),
        "final_norm.scale": (d,),
        "layers.attn_norm.scale": (L, d),
        "layers.mlp_norm.scale": (L, d),
        "layers.wq": (L, d, s.heads * hd),
        "layers.wk": (L, d, s.kv_heads * hd),
        "layers.wv": (L, d, s.kv_heads * hd),
        "layers.wo": (L, s.heads * hd, d),
        "layers.w_up": (L, d, s.ff),
        "layers.w_down": (L, s.ff, d),
    }
    if s.gated:
        out["layers.w_gate"] = (L, d, s.ff)
    if not s.tied:
        out["lm_head"] = (d, s.vocab)
    if s.layernorm:
        for k in ("final_norm", "layers.attn_norm", "layers.mlp_norm"):
            out[k + ".bias"] = out[k + ".scale"]
    return out


def _leaf(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        v = 0.02 * z
    elif name.endswith(".scale"):
        v = 1.0 + 0.1 * z
    elif name.endswith(".bias"):
        v = 0.1 * z
    else:  # a matrix (..., fan_in, fan_out)
        v = z / math.sqrt(shape[-2])
    return v.astype(dtype)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    lo, hi = np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)


def make(s: Spec, seed: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Every weight of ``s`` from ``seed``, on the default device."""
    names = sorted(shapes(s).items())

    def build(key):
        return {n: _leaf(jax.random.fold_in(key, i), n, shp, dtype)
                for i, (n, shp) in enumerate(names)}

    return jax.jit(build)(seed_key(seed))
