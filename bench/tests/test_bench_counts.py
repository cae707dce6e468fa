"""bench/counts.py against numbers worked out by hand for granite-3-2b."""
import json

import pytest

from bench import counts
from bench.tests.conftest import REPO


def spec(name):
    return counts.Spec.from_config(
        json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text()))


def test_granite_layer_and_weights():
    s = spec("granite-3-2b-serve")
    # wq + wo: 2 * 2048 * 2048; wk + wv: 2 * 2048 * 512; SwiGLU: 3 * 2048 * 8192
    assert s.layer_matmul_params == 8_388_608 + 2_097_152 + 50_331_648
    # 40 layers with two RMSNorm scales, the final norm, the tied embedding
    assert s.weight_bytes == 4 * (40 * (60_817_408 + 4096) + 2048 + 49155 * 2048)
    assert s.weight_bytes == 10_134_126_592
    assert s.kv_token_bytes == 40 * 2 * 8 * 64 * 4 == 160 * 1024


def test_granite_train_step_flops():
    s = spec("granite-3-2b-train")
    matmuls = 2 * 4096 * 4 * 60_817_408
    attention = 4 * 4 * 64 * 32 * 2 * (2048 * 2049 // 2)
    head = 2 * 2 * 2047 * 2048 * 49155
    assert counts.train_step_flops(s, 2, 2048) == 3 * (matmuls + attention + head)
    assert counts.train_step_flops(s, 2, 2048) == pytest.approx(8.864e12, rel=1e-3)


def test_flash_kernels_count_causal_pairs_without_recompute():
    s = spec("granite-3-2b-train")
    fwd = counts.flash_forward(s, 2, 2048)
    bwd = counts.flash_backward(s, 2, 2048)
    pairs = 2048 * 2049 // 2
    assert fwd.flops == 4 * 64 * 32 * 2 * pairs
    assert bwd.flops == 2 * fwd.flops
    q, kv = 2 * 2048 * 32 * 64, 2 * 2048 * 8 * 64
    assert fwd.bytes == 4 * (2 * q + 2 * kv + 2 * 32 * 2048)


def test_serving_programs_read_weights_once_and_live_kv_only():
    s = spec("granite-3-2b-serve")
    peak = counts.load_peaks("TPU v5 lite")
    layers = 40 * (60_817_408 + 4096) * 4
    head = 49155 * 2048 * 4
    chunk = counts.prefill_chunk(s, start=32, n_valid=16)
    assert chunk.bytes == layers + 16 * 2048 * 4 + 48 * s.kv_token_bytes \
        + 16 * s.kv_token_bytes + head
    ro = counts.prefill_chunk(s, start=32, n_valid=16, write=False)
    assert chunk.bytes - ro.bytes == 16 * s.kv_token_bytes
    step = counts.decode_step(s, [100, 300])
    assert step.flops == 2 * 2 * 40 * 60_817_408 + 40 * 4 * 64 * 32 * 400 \
        + 2 * 2 * 2048 * 49155
    assert step.bytes == layers + 2 * 2048 * 4 + (400 + 2) * s.kv_token_bytes + head
    # one decode step is bound by reading 10.1 GB of weights: about 12.4 ms
    assert step.least_s(peak) == pytest.approx(step.bytes / 819e9)
    assert 0.012 < step.least_s(peak) < 0.0126
    attn = counts.paged_decode_attention(s, [100, 300])
    assert attn.bytes == 40 * (400 * 2 * 8 * 64 * 4 + 2 * 2 * 32 * 64 * 4)
    assert counts.decode_step(s, []) == counts.ZERO


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        counts.load_peaks("cpu")
    assert counts.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
