"""Operations and bytes of the algorithm, from shapes alone.

Counts follow the algorithm, not its implementation: no recompute, only the
slots that are active, weights in their stored dtype and read once per
program call, KV for the live positions only, and no copies of the weights
in another dtype. A kernel rewritten later keeps the same count.

``Spec`` is read from a configuration file's ``model`` section (Hugging
Face key names).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Sequence

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


@dataclasses.dataclass(frozen=True)
class Spec:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    gated: bool
    tied: bool
    layernorm: bool
    param_bytes: int = 4
    kv_bytes: int = 4

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        m = cfg["model"]
        heads = m["num_attention_heads"]
        return cls(
            d=m["hidden_size"], layers=m["num_hidden_layers"], heads=heads,
            kv_heads=m["num_key_value_heads"],
            head_dim=m.get("head_dim") or m["hidden_size"] // heads,
            ff=m["intermediate_size"], vocab=m["vocab_size"],
            gated=m["hidden_act"] != "relu2",
            tied=bool(m["tie_word_embeddings"]),
            layernorm=m["norm"] == "layernorm",
            param_bytes=DTYPE_BYTES[cfg["precision"]["params"]],
            kv_bytes=DTYPE_BYTES[cfg["precision"].get("kv_cache",
                                                      cfg["precision"]["params"])])

    # -- parameters ----------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        attn = self.d * self.heads * self.head_dim * 2 \
            + self.d * self.kv_heads * self.head_dim * 2
        mlp = (3 if self.gated else 2) * self.d * self.ff
        return attn + mlp

    @property
    def layer_params(self) -> int:
        return self.layer_matmul_params + (4 if self.layernorm else 2) * self.d

    @property
    def weight_bytes(self) -> int:
        """Every stored weight: layers, final norm, embedding and head."""
        emb = self.vocab * self.d * (1 if self.tied else 2)
        norm = (2 if self.layernorm else 1) * self.d
        return (self.layers * self.layer_params + norm + emb) * self.param_bytes

    @property
    def kv_token_bytes(self) -> int:
        """KV of one position across every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim * self.kv_bytes


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peak: dict) -> float:
        """Roofline least time: the larger of compute and memory time."""
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


ZERO = Work(0.0, 0.0)


def _causal_pairs(start: int, n: int) -> int:
    """(query, key) pairs for queries at positions start..start+n-1 that
    attend causally to every position up to their own."""
    return n * start + n * (n + 1) // 2


# -- training ------------------------------------------------------------------

def train_step_flops(s: Spec, batch: int, seq: int) -> float:
    """Model FLOPs of one step: forward plus backward (twice the forward),
    with the loss over seq - 1 predicted positions and no recompute."""
    tokens = batch * seq
    fwd = 2 * tokens * s.layers * s.layer_matmul_params
    fwd += s.layers * 4 * s.head_dim * s.heads * batch * _causal_pairs(0, seq)
    fwd += 2 * batch * (seq - 1) * s.d * s.vocab
    return 3 * fwd


def flash_forward(s: Spec, batch: int, seq: int) -> Work:
    """One layer's causal flash attention forward: QK^T and PV over the
    causal pairs; reads q, k, v once and writes o and the log-sum-exp."""
    act = 4  # the kernels take float32 activations
    flops = 4 * s.head_dim * s.heads * batch * _causal_pairs(0, seq)
    q = batch * seq * s.heads * s.head_dim
    kv = 2 * batch * seq * s.kv_heads * s.head_dim
    return Work(flops, act * (2 * q + kv + batch * s.heads * seq))


def flash_backward(s: Spec, batch: int, seq: int) -> Work:
    """One layer's flash backward (dq and dkv kernels together): dV, dP, dQ
    and dK over the causal pairs, twice the forward's matmuls; the scores'
    recompute is not counted. Reads q, k, v, o, dO and lse; writes dq, dk,
    dv."""
    act = 4
    flops = 8 * s.head_dim * s.heads * batch * _causal_pairs(0, seq)
    q = batch * seq * s.heads * s.head_dim
    kv = 2 * batch * seq * s.kv_heads * s.head_dim
    return Work(flops, act * (4 * q + 2 * kv + batch * s.heads * seq))


# -- serving -------------------------------------------------------------------

def _head(s: Spec, positions: int) -> Work:
    return Work(2 * positions * s.d * s.vocab, s.vocab * s.d * s.param_bytes)


def prefill_chunk(s: Spec, start: int, n_valid: int, write: bool = True) -> Work:
    """One prompt chunk of ``n_valid`` real tokens at positions
    start..start+n_valid-1: every layer's matmuls, attention over the live
    prefix, logits of the last position only. Reads every weight once and the
    KV of every live position; writes the chunk's KV unless read-only."""
    f = 2 * n_valid * s.layers * s.layer_matmul_params
    f += s.layers * 4 * s.head_dim * s.heads * _causal_pairs(start, n_valid)
    b = s.layers * s.layer_params * s.param_bytes + n_valid * s.d * s.param_bytes
    b += (start + n_valid) * s.kv_token_bytes
    if write:
        b += n_valid * s.kv_token_bytes
    return Work(f, b) + _head(s, 1)


def decode_step(s: Spec, contexts: Sequence[int]) -> Work:
    """One batched decode step over the active slots; ``contexts`` are the
    positions each attends to (its cache length after the new token). Reads
    every weight once and the KV of every live position; writes one
    position per slot."""
    n = len(contexts)
    if n == 0:
        return ZERO
    live = sum(contexts)
    f = 2 * n * s.layers * s.layer_matmul_params
    f += s.layers * 4 * s.head_dim * s.heads * live
    b = s.layers * s.layer_params * s.param_bytes + n * s.d * s.param_bytes
    b += (live + n) * s.kv_token_bytes
    return Work(f, b) + _head(s, n)


def paged_decode_attention(s: Spec, contexts: Sequence[int]) -> Work:
    """The paged decode kernel over every layer of one step: each active
    slot's query against its live KV blocks."""
    live = sum(contexts)
    n = len(contexts)
    f = s.layers * 4 * s.head_dim * s.heads * live
    b = s.layers * (live * 2 * s.kv_heads * s.head_dim * s.kv_bytes
                    + 2 * n * s.heads * s.head_dim * 4)
    return Work(f, b)


def load_peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind`` from ``peaks.json``; an unknown device is an
    error, never a default."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
