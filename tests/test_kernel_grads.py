"""Gradient parity of the custom_vjp Pallas kernels vs the jnp oracles.

``jax.grad`` through ``attention_impl(..., impl="pallas")`` must match the
naive oracle — across causal/non-causal, GQA (K < H, including MQA), and
sequence lengths that do not divide into the planned blocks (which force the
ragged-divisor block path), several heads a grid step, causal triangles of
several blocks whose diagonal tiles straddle, and queries offset into a
longer key sequence. rmsnorm grads check against kernels/ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_mha
from repro.models.attention import attention_impl, naive_attention

KEY = jax.random.PRNGKey(42)

TOL = dict(rtol=2e-2, atol=2e-2)


def _qkv(B, Sq, Sk, H, K, hd):
    q = jax.random.normal(KEY, (B, Sq, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Sk, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Sk, K, hd))
    return q, k, v


def _grads(impl, q, k, v, causal, w, q_offset=0):
    """The output and the gradient of ``sum(out * w)``."""
    out, vjp = jax.vjp(lambda q, k, v: attention_impl(
        q, k, v, causal=causal, q_offset=q_offset, impl=impl), q, k, v)
    return out, vjp(w.astype(out.dtype))


def _case(*shape, q_offset=0):
    return pytest.param(*shape, q_offset, id="-".join(map(str, shape)) + (
        f"-offset{q_offset}" if q_offset else ""))


@pytest.mark.parametrize("B,Sq,Sk,H,K,hd,q_offset", [
    _case(1, 16, 16, 4, 4, 32),     # MHA
    _case(2, 37, 37, 8, 4, 16),     # GQA, ragged (block != divisor of 128)
    _case(1, 128, 128, 8, 2, 64),   # GQA at exactly one default block
    _case(1, 256, 256, 4, 1, 32),   # MQA, multi-block (causal tile skipping live)
    _case(1, 48, 112, 4, 2, 32),    # Sq != Sk (cross-attention shape)
    _case(1, 1100, 1100, 4, 2, 64),  # ragged blocks, 2 heads a step, straddling
    _case(1, 1536, 1536, 2, 1, 128),  # hd 128, a causal triangle of blocks
    _case(2, 96, 160, 4, 4, 64, q_offset=64),  # queries end the key sequence
    _case(1, 640, 1152, 4, 2, 32, q_offset=512),  # offset across blocks
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_parity(B, Sq, Sk, H, K, hd, q_offset, causal):
    q, k, v = _qkv(B, Sq, Sk, H, K, hd)
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Sq, H, hd))
    op, gp = _grads("pallas", q, k, v, causal, w, q_offset)
    on, gn = _grads("naive", q, k, v, causal, w, q_offset)
    np.testing.assert_allclose(np.asarray(op), np.asarray(on), **TOL,
                               err_msg="output mismatch")
    for name, a, b in zip("qkv", gp, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL,
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_grad_forced_small_blocks():
    """Multi-tile path in both grid dims, with causal tile skipping."""
    B, H, Sq, Sk, hd = 1, 3, 64, 96, 32
    q = jax.random.normal(KEY, (B, H, Sq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, Sk, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, Sk, hd))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (B, H, Sq, hd))

    def f(q, k, v):
        out = flash_attention_mha(q, k, v, causal=True, block_q=16, block_k=16)
        return (out * w).sum()

    def f_ref(q, k, v):
        out = naive_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True)
        return (out.transpose(0, 2, 1, 3) * w).sum()

    gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gp, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL,
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_lse_residual_matches_logsumexp():
    """flash_attention_fwd_lse's residual rows are the masked score LSE."""
    from repro.kernels.flash_attention import flash_attention_fwd_lse
    B, H, Sq, Sk, hd = 1, 3, 64, 96, 32
    q = jax.random.normal(KEY, (B, H, Sq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, Sk, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, Sk, hd))
    o, lse = flash_attention_fwd_lse(q, k, v, causal=True, block_q=16, block_k=16)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
    mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
    s = jnp.where(mask[None, None], s, -1e30)
    want_lse = jax.scipy.special.logsumexp(s, axis=-1)
    want_o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_fwd_value_through_vjp_wrapper():
    """The custom_vjp primal (not just the fwd rule) must match the oracle."""
    q, k, v = _qkv(2, 64, 64, 8, 4, 32)
    got = attention_impl(q, k, v, causal=True, impl="pallas")
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(1, 7, 64), (4, 3, 96), (3, 17, 256)])
def test_rmsnorm_grad_parity(shape):
    x = jax.random.normal(KEY, shape)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), shape[-1:])
    w = jax.random.normal(jax.random.fold_in(KEY, 2), shape)

    def loss(fn):
        return lambda x, s: (fn(x, s).astype(jnp.float32) * w).sum()

    gx, gs = jax.grad(loss(ops.rmsnorm), argnums=(0, 1))(x, s)
    rx, rs = jax.grad(loss(ref.ref_rmsnorm), argnums=(0, 1))(x, s)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(rs), rtol=1e-4, atol=1e-4)


def test_rmsnorm_grad_bf16_inputs():
    x = jax.random.normal(KEY, (4, 96), jnp.bfloat16)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (96,), jnp.bfloat16)
    gx, gs = jax.grad(lambda x, s: ops.rmsnorm(x, s).astype(jnp.float32).sum(),
                      argnums=(0, 1))(x, s)
    rx, rs = jax.grad(lambda x, s: ref.ref_rmsnorm(x, s).astype(jnp.float32).sum(),
                      argnums=(0, 1))(x, s)
    np.testing.assert_allclose(np.asarray(gx, np.float32), np.asarray(rx, np.float32),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(gs, np.float32), np.asarray(rs, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_train_step_through_pallas_attention():
    """End-to-end: loss + grad of a tiny GQA block with impl='pallas'."""
    from repro.configs import get_config
    from repro.models.attention import attn_params, gqa_forward
    cfg = get_config("llama3.2-1b").reduced()
    p = attn_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))

    def loss(p, impl):
        y = gqa_forward(p, x, cfg, positions=pos, impl=impl)
        return (y ** 2).mean()

    gp = jax.grad(lambda p: loss(p, "pallas"))(p)
    gn = jax.grad(lambda p: loss(p, "naive"))(p)
    flat_p = jax.tree_util.tree_leaves(gp)
    flat_n = jax.tree_util.tree_leaves(gn)
    for a, b in zip(flat_p, flat_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
