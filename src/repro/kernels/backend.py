"""Backend autodetection shared by every Pallas kernel wrapper (via ops.py).

Mosaic only lowers on a real TPU backend; everywhere else (the CPU CI
container, GPU hosts) the kernels run in Pallas interpret mode. Kernel entry
points take ``interpret=None`` and resolve it here so no call site hardcodes
a backend assumption.
"""
from __future__ import annotations

import os
from typing import Optional

import jax


def default_interpret() -> bool:
    """True when the current backend cannot lower Mosaic (i.e. not a TPU)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def attn_bf16(lowp: Optional[bool] = None) -> bool:
    """bf16 score/probability accumulation toggle for the flash kernels.

    Mirrors the chunked path's ``REPRO_ATTN_BF16``: dot-product *inputs* drop
    to bf16 (halving the dominant VMEM/HBM traffic) while the online-softmax
    statistics and the output accumulator stay f32. Resolved eagerly in the
    non-jitted wrappers so flipping the env var between calls takes effect.
    """
    if lowp is not None:
        return bool(lowp)
    return os.environ.get("REPRO_ATTN_BF16", "0") == "1"


def auto_attn_impl(seq_len: int, *, interpret: Optional[bool] = None) -> str:
    """Attention-kernel policy for ``--attn-impl auto``.

    Policy table (seq length x backend capability):
      - short sequences: ``naive`` — exact, no tiling overhead, and the O(S^2)
        score matrix is small enough to materialize;
      - long sequences on a backend that can lower Mosaic (real TPU,
        ``interpret`` False): ``pallas`` — the training-fit flash kernel with
        the custom_vjp backward;
      - long sequences everywhere else (CPU/GPU, interpret mode): ``chunked``
        — the jnp online-softmax fallback; interpreted Pallas would be
        orders of magnitude slower than the same math in jnp.
    """
    if seq_len <= 512:
        return "naive"
    return "chunked" if resolve_interpret(interpret) else "pallas"


def auto_decode_impl(cache_len: int, *, interpret: Optional[bool] = None) -> str:
    """Decode-attention policy for ``--attn-impl auto`` in the serve path.

    Decode latency is KV-bandwidth-bound, so the crossover is governed by how
    much cache a step streams, not by compute:
      - short caches: ``naive`` — a single (H, cache_len) score row is cheap
        and exact, and kernel launch/tiling overhead would dominate;
      - long caches on a backend that can lower Mosaic: ``pallas`` — the
        single-query flash-decode kernel streams only the ``cache_len``-valid
        KV tiles and shares each KV head across its GQA query group;
      - long caches in interpret mode (CPU/GPU CI): ``naive`` — interpreted
        Pallas is orders of magnitude slower than the same math in jnp.
    """
    if cache_len < 512:
        return "naive"
    return "naive" if resolve_interpret(interpret) else "pallas"


def divisor_block(size: int, preferred: int) -> int:
    """Largest block <= preferred that divides size (handles ragged dims)."""
    b = min(preferred, size)
    while size % b:
        b -= 1
    return b
