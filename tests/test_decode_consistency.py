"""KV-cache decode == teacher-forced forward, token by token, for every
decoder arch (high MoE capacity so no tokens drop); plus pallas-vs-naive
decode parity (GQA/MLA, ragged per-sequence cache_lens, bf16 caches)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED
from repro.models import build_model

KEY = jax.random.PRNGKey(0)

TOL = {"zamba2-2.7b": 5e-3, "rwkv6-7b": 5e-3}


@pytest.mark.parametrize("arch", sorted(a for a in ASSIGNED if a != "whisper-small"))
def test_decode_matches_forward(arch):
    cfg = ASSIGNED[arch].reduced()
    model = build_model(cfg, impl="naive", moe_cf=100.0)
    params = model.init(KEY)
    B, S = 2, 8
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embed"] = jax.random.normal(KEY, (B, cfg.n_image_tokens,
                                                       cfg.d_model)) * 0.02
    full = model.forward(params, batch)
    cache = model.init_cache(B, S, jnp.float32)
    outs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, batch["tokens"][:, t:t + 1],
                                      jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    tol = TOL.get(arch, 2e-3)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), rtol=tol, atol=tol)


def test_whisper_decode_matches_forward():
    from repro.models import encdec as E
    cfg = ASSIGNED["whisper-small"].reduced()
    model = build_model(cfg, impl="naive")
    params = model.init(KEY)
    B, S = 2, 8
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
             "audio_embed": jax.random.normal(KEY, (B, cfg.n_audio_frames,
                                                    cfg.d_model)) * 0.02}
    full = model.forward(params, batch)
    cache = model.init_cache(B, S, jnp.float32)
    enc_h = E.encode(params, cfg, batch["audio_embed"])
    ks, vs = [], []
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a, i=i: a[i], params["dec_layers"])
        hd = cfg.head_dim
        # cross KV in the head-major cache layout decode reads
        ks.append((enc_h @ lp["cross_attn"]["wk"]).reshape(
            B, -1, cfg.n_kv_heads, hd).swapaxes(1, 2))
        vs.append((enc_h @ lp["cross_attn"]["wv"]).reshape(
            B, -1, cfg.n_kv_heads, hd).swapaxes(1, 2))
    cache["cross"] = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
    outs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, batch["tokens"][:, t:t + 1],
                                      jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), rtol=2e-3, atol=2e-3)


def test_prefill_matches_forward_logits():
    cfg = ASSIGNED["llama3.2-1b"].reduced()
    model = build_model(cfg, impl="naive")
    params = model.init(KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)}
    full = model.forward(params, batch)
    pl, cache = model.prefill(params, batch)
    np.testing.assert_allclose(np.asarray(full[:, -1:]), np.asarray(pl),
                               rtol=1e-5, atol=1e-5)
    assert cache["k"].shape[0] == cfg.n_layers


# ---------------------------------------------------------------------------
# pallas single-query decode kernel vs the naive oracle
# ---------------------------------------------------------------------------


def _flash_decode_ref(q, k, v, lengths, scale=None):
    """float64 numpy oracle for the grouped single-query kernel."""
    B, K, G, hd = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    outs = []
    for b in range(B):
        L = int(lengths[b])
        if L == 0:
            outs.append(np.zeros((K, G, v.shape[-1])))
            continue
        s = np.einsum("kgh,skh->kgs", np.asarray(q[b], np.float64),
                      np.asarray(k[b, :L], np.float64)) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("kgs,skv->kgv", p, np.asarray(v[b, :L], np.float64)))
    return np.stack(outs).astype(np.float32)


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_ragged_vs_ref(cache_dtype):
    from repro.kernels.flash_attention import flash_decode
    B, Smax, K, G, hd = 3, 40, 2, 4, 16
    q = jax.random.normal(KEY, (B, K, G, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Smax, K, hd)).astype(cache_dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Smax, K, hd)).astype(cache_dtype)
    lengths = jnp.array([7, 40, 0], jnp.int32)  # ragged, incl. an idle slot
    # the kernel reads the head-major cache; the oracle is sequence-major
    out = np.asarray(flash_decode(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                                  lengths, block_k=16))
    ref = _flash_decode_ref(q, k.astype(jnp.float32), v.astype(jnp.float32),
                            np.asarray(lengths))
    tol = 2e-6 if cache_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    assert np.all(out[2] == 0.0)  # length-0 rows are zeros, not NaN


def test_flash_decode_bf16_accumulation_toggle():
    from repro.kernels.flash_attention import flash_decode
    B, Smax, K, G, hd = 2, 32, 2, 2, 16
    q = jax.random.normal(KEY, (B, K, G, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, K, Smax, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, K, Smax, hd))
    lengths = jnp.array([32, 17], jnp.int32)
    exact = np.asarray(flash_decode(q, k, v, lengths, block_k=16, lowp=False))
    lowp = np.asarray(flash_decode(q, k, v, lengths, block_k=16, lowp=True))
    assert np.all(np.isfinite(lowp))
    # bf16 dot inputs: close to f32 but not bit-identical
    np.testing.assert_allclose(lowp, exact, rtol=3e-2, atol=3e-2)
    assert np.abs(lowp - exact).max() > 0.0


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ragged", [False, True])
def test_gqa_decode_pallas_matches_naive(cache_dtype, ragged):
    from repro.models.attention import gqa_decode, gqa_params
    cfg = ASSIGNED["llama3.2-1b"].reduced()
    p = gqa_params(KEY, cfg)
    B, Smax = 3, 24
    x = jax.random.normal(KEY, (B, 1, cfg.d_model)) * 0.1
    pre = jax.random.normal(jax.random.fold_in(KEY, 3), (B, cfg.n_kv_heads, Smax,
                                                         cfg.head_dim)) * 0.3
    cache = {"k": pre.astype(cache_dtype), "v": (pre * 0.7).astype(cache_dtype)}
    cl = jnp.array([5, 23, 1], jnp.int32) if ragged else jnp.int32(6)
    y_n, c_n = gqa_decode(p, x, cache, cl, cfg, impl="naive")
    y_p, c_p = gqa_decode(p, x, cache, cl, cfg, impl="pallas")
    tol = 1e-5 if cache_dtype == jnp.float32 else 5e-3
    np.testing.assert_allclose(np.asarray(y_n), np.asarray(y_p), rtol=tol, atol=tol)
    # the cache update is impl-independent
    np.testing.assert_allclose(np.asarray(c_n["k"]), np.asarray(c_p["k"]))


@pytest.mark.parametrize("ragged", [False, True])
def test_mla_decode_pallas_matches_naive(ragged):
    from repro.models.attention import init_mla_cache, mla_decode, mla_params
    cfg = ASSIGNED["deepseek-v3-671b"].reduced()
    p = mla_params(KEY, cfg)
    B, Smax = 3, 24
    x = jax.random.normal(KEY, (B, 1, cfg.d_model)) * 0.1
    cache = init_mla_cache(cfg, B, Smax, jnp.float32)
    cache = {"latent": jax.random.normal(jax.random.fold_in(KEY, 4),
                                         cache["latent"].shape) * 0.3,
             "k_rope": jax.random.normal(jax.random.fold_in(KEY, 5),
                                         cache["k_rope"].shape) * 0.3}
    cl = jnp.array([4, 23, 2], jnp.int32) if ragged else jnp.int32(7)
    y_n, c_n = mla_decode(p, x, cache, cl, cfg, impl="naive")
    y_p, c_p = mla_decode(p, x, cache, cl, cfg, impl="pallas")
    np.testing.assert_allclose(np.asarray(y_n), np.asarray(y_p),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(c_n["latent"]), np.asarray(c_p["latent"]))


@pytest.mark.parametrize("arch", ["llama3.2-1b", "deepseek-v3-671b"])
def test_model_decode_pallas_token_identical(arch):
    """Greedy decode through the full stack: pallas == naive, token for token."""
    from repro.launch.steps import greedy_decode_tokens
    cfg = ASSIGNED[arch].reduced()
    toks = jax.random.randint(KEY, (2, 1), 0, cfg.vocab_size)
    streams = {}
    for impl in ("naive", "pallas"):
        model = build_model(cfg, impl=impl, moe_cf=100.0)
        params = model.init(KEY)
        streams[impl] = greedy_decode_tokens(model, params, toks, steps=4,
                                             max_len=8)
    np.testing.assert_array_equal(streams["naive"], streams["pallas"])


def test_auto_decode_impl_policy():
    from repro.kernels.backend import auto_decode_impl
    assert auto_decode_impl(128, interpret=False) == "naive"
    assert auto_decode_impl(512, interpret=False) == "pallas"  # gate regime
    assert auto_decode_impl(2048, interpret=False) == "pallas"
    assert auto_decode_impl(2048, interpret=True) == "naive"
