"""Model FLOPs of a layer-pattern hybrid's training step, from shapes
alone, counted as ``bench/counts.py`` counts the dense decoder's: the
algorithm's work, no recompute, only the causal pairs."""
from __future__ import annotations

from bench.counts import _causal_pairs
from bench.hybrid import Dims


def layer_matmul_params(m: Dims, kind: str) -> int:
    """A layer's matmul parameters: its mixer's projections and the MLP."""
    mlp = 3 * m.d * m.ff
    if kind == "mamba":
        return m.d * m.in_proj + m.d_inner * m.d + mlp
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return m.d * (q + 2 * kv) + q * m.d + mlp


def ssd_flops(m: Dims, seq: int) -> int:
    """One Mamba2 layer's SSD over one sequence at the configuration's
    chunk: in each chunk, C.B over its causal pairs for each group and their
    weighted sum of x for each head; across chunks, each position's read of
    the carried state (C . h) and its update (B x^T), N x head_dim each per
    head."""
    flops = 0
    for start in range(0, seq, m.chunk):
        n = min(m.chunk, seq - start)
        flops += 2 * _causal_pairs(0, n) * (m.groups * m.d_state + m.d_inner)
        flops += 4 * n * m.d_state * m.d_inner
    return flops


def train_step_flops(m: Dims, batch: int, seq: int) -> float:
    """Forward plus backward (twice the forward): every layer's matmuls by
    kind, attention over the causal pairs, the SSD, and the tied head over
    the vocabulary slice for the seq - 1 predicted positions."""
    fwd = 0
    for kind in m.layer_types:
        fwd += 2 * batch * seq * layer_matmul_params(m, kind)
        if kind == "mamba":
            fwd += batch * ssd_flops(m, seq)
        else:
            fwd += 4 * m.head_dim * m.heads * batch * _causal_pairs(0, seq)
    fwd += 2 * batch * (seq - 1) * m.d * m.vocab
    return 3.0 * fwd
