"""Per-kernel shape/dtype sweeps vs the ref.py jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(1, 7, 64), (4, 3, 96), (2, 1, 128), (3, 17, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), shape[-1:], dtype)
    got = ops.rmsnorm(x, s)
    want = ref.ref_rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape,k", [((2, 8, 8, 16), 3), ((1, 13, 11, 24), 3),
                                     ((2, 16, 16, 8), 5), ((1, 32, 32, 32), 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_depthwise_sweep(shape, k, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (k, k, shape[-1]), dtype)
    got = ops.depthwise_conv(x, w)
    want = ref.ref_depthwise_conv(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,K,hd", [(1, 16, 4, 4, 32), (2, 37, 8, 4, 16),
                                        (1, 128, 8, 2, 64), (2, 64, 4, 1, 32),
                                        (1, 1100, 4, 2, 64), (1, 1536, 2, 1, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, K, hd, causal):
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, K, hd))
    from repro.models.attention import naive_attention
    got = ops.flash_attention(q, k, v, causal=causal)
    want = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def _causal_tiles(plan):
    """Tiles of the (Sq, Sk) score matrix that hold a visible entry."""
    q_pos = np.arange(plan.Sq) + plan.q_offset
    seen = np.arange(plan.Sk)[None, :] <= q_pos[:, None]
    return int(seen.reshape(plan.Sq // plan.bq, plan.bq, plan.Sk // plan.bk,
                            plan.bk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("B,H,Sq,Sk,hd,q_offset", [
    (2, 32, 2048, 2048, 64, 0),     # granite-3-2b's training step
    (2, 32, 2048, 2048, 128, 0),    # head_dim 128 (nemotron-4-15b)
    (1, 4, 1100, 1100, 64, 0),      # ragged blocks
    (2, 4, 640, 1152, 32, 512),     # queries offset into longer keys
])
def test_train_plan(B, H, Sq, Sk, hd, q_offset):
    """The grid plan runs only causal tiles, within its VMEM limit, and the
    wrapper's ``flash_grid_steps`` gauge reads the plan's step counts."""
    from repro import obs
    from repro.kernels import flash_attention as fa
    plan = fa._train_plan(B, H, Sq, Sk, hd, q_offset, True)
    assert H % plan.hb == 0
    prev = obs.set_telemetry(obs.Telemetry(enabled=True))
    try:
        x = jax.ShapeDtypeStruct((B, H, Sq, hd), jnp.float32)
        kv = jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32)
        jax.eval_shape(lambda q, k, v: fa.flash_attention_mha(
            q, k, v, q_offset=q_offset), x, kv, kv)
        gauge = obs.get_telemetry().metrics.get("flash_grid_steps")
    finally:
        obs.set_telemetry(prev)
    for kernel in ("fwd", "dq", "dkv"):
        total, live = plan.grid_steps(kernel)
        assert live == total == B * (H // plan.hb) * _causal_tiles(plan)
        assert gauge.value(kernel=kernel, live=1) == live
        assert gauge.value(kernel=kernel, live=0) == 0
        assert plan.vmem_bytes(kernel) <= (plan.vmem_limit or fa._SCOPED_VMEM)
        if Sq == 2048:
            assert total <= 1024  # 16,384 at 128 x 128 tiles, a head a step


def test_train_plan_dead_steps_zero_fill():
    """Under causal, KV blocks past the last query get one dead step each
    (it writes their zero dk, dv); the forward and dq need none."""
    from repro.kernels import flash_attention as fa
    plan = fa._train_plan(1, 3, 48, 112, 32, 0, True, block_q=16, block_k=16)
    assert plan.grid_steps("fwd") == plan.grid_steps("dq") == (6, 6)
    assert plan.grid_steps("dkv") == (6 + 4, 6)  # KV blocks 3..6 see nothing


def test_flash_attention_bf16():
    q = jax.random.normal(KEY, (1, 64, 4, 32), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 64, 2, 32), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 64, 2, 32), jnp.bfloat16)
    from repro.models.attention import naive_attention
    got = ops.flash_attention(q, k, v, causal=True)
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)


def test_flash_attention_bf16_env_toggle(monkeypatch):
    """REPRO_ATTN_BF16 reaches the Pallas kernels: bf16 dot inputs, f32
    statistics — close to the exact path, resolved per call (no stale jit)."""
    B, S, H, hd = 1, 32, 2, 16
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 8), (B, S, H, hd))
    monkeypatch.delenv("REPRO_ATTN_BF16", raising=False)
    exact = np.asarray(ops.flash_attention(q, k, v, causal=True))
    monkeypatch.setenv("REPRO_ATTN_BF16", "1")
    lowp = np.asarray(ops.flash_attention(q, k, v, causal=True))
    assert np.all(np.isfinite(lowp))
    np.testing.assert_allclose(lowp, exact, rtol=3e-2, atol=3e-2)
    assert np.abs(lowp - exact).max() > 0.0

    # grads flow through the lowp backward kernels too
    g = jax.grad(lambda q: ops.flash_attention(q, k, v, causal=True).sum())(q)
    assert np.all(np.isfinite(np.asarray(g)))
