"""The layer-pattern hybrid (granite-4.0-h): the registered config builds
the published pattern, the Mamba2 scan matches its recurrence with a conv
bias at d_state > 16, the flash training kernels take an explicit softmax
scale, and reduced configs keep both layer kinds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_attention_mha
from repro.models import build_model, ssm
from repro.models.attention import naive_attention

KEY = jax.random.PRNGKey(7)


@pytest.fixture
def telemetry():
    tel = obs.Telemetry(enabled=True)
    prev = obs.set_telemetry(tel)
    yield tel
    obs.set_telemetry(prev)


def test_published_pattern_builds_in_runs():
    cfg = get_config("granite-4.0-h-micro")
    shapes = jax.eval_shape(build_model(cfg).init, KEY)
    runs = [("mamba" if "mamba" in r else "attention", r["ln1"]["scale"].shape[0])
            for r in shapes["runs"]]
    assert runs == [("mamba", 5), ("attention", 1), ("mamba", 9), ("attention", 1),
                    ("mamba", 9), ("attention", 1), ("mamba", 9), ("attention", 1),
                    ("mamba", 4)]
    mamba = shapes["runs"][0]["mamba"]
    assert mamba["w_in"].shape == (5, 2048, 2 * 4096 + 2 * 128 + 64)
    assert mamba["b_conv"].shape == (5, 4096 + 2 * 128)
    assert shapes["embed"].shape == (100352, 2048) and "lm_head" not in shapes


def test_reduced_keeps_both_layer_kinds():
    r = get_config("granite-4.0-h-micro").reduced()
    assert r.layer_types == ("mamba", "attention") and r.n_layers == 2
    runs = build_model(r).init(KEY)["runs"]
    assert ["mamba" in p for p in runs] == [True, False]
    with pytest.raises(ValueError):
        dataclasses.replace(r, n_layers=3)


def test_gauges_are_set_when_the_model_traces(telemetry):
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro").reduced(),
                              n_layers=3, layer_types=("mamba", "attention", "mamba"))
    model = build_model(cfg, impl="naive")
    params = model.init(KEY)
    jax.eval_shape(model.loss, params, {"tokens": jnp.zeros((1, 600), jnp.int32)})
    line = telemetry.metrics.snapshot_line(0)["metrics"]
    assert line["hybrid_layers{kind=mamba}"] == 2
    assert line["hybrid_layers{kind=attention}"] == 1
    assert line["ssd_chunks{chunk=256}"] == 3  # 600 positions in chunks of 256


def test_serving_a_layer_pattern_is_refused():
    model = build_model(get_config("granite-4.0-h-micro").reduced())
    with pytest.raises(ValueError, match="layer pattern"):
        model.init_cache(1, 8)


def _recurrence(p, x, cfg):
    """Mamba2 one position at a time, written out: the causal conv with its
    bias, h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t + D x_t,
    the gated RMSNorm and out_proj."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    d_inner, hd, nh, g, N, conv_ch = ssm.mamba2_dims(cfg)
    W = cfg.ssm_conv_width
    B, S, _ = x.shape
    proj = x @ p["w_in"]
    z, xbc, dt_raw = (proj[..., :d_inner], proj[..., d_inner:d_inner + conv_ch],
                      proj[..., d_inner + conv_ch:])
    A = -np.exp(p["A_log"])
    h = np.zeros((B, nh, N, hd))
    ys = []
    for t in range(S):
        window = [xbc[:, t - W + 1 + i] if t - W + 1 + i >= 0 else 0.0 for i in range(W)]
        u = sum(w_i * p["w_conv"][i] for i, w_i in enumerate(window)) + p["b_conv"]
        u = u / (1 + np.exp(-u))
        xs = u[:, :d_inner].reshape(B, nh, hd)
        Bm = np.repeat(u[:, d_inner:d_inner + g * N].reshape(B, g, N), nh // g, 1)
        Cm = np.repeat(u[:, d_inner + g * N:].reshape(B, g, N), nh // g, 1)
        dt = np.log1p(np.exp(dt_raw[:, t] + p["dt_bias"]))
        h = np.exp(dt * A)[..., None, None] * h \
            + dt[..., None, None] * Bm[..., :, None] * xs[..., None, :]
        y = np.einsum("bhn,bhnd->bhd", Cm, h) + p["D"][:, None] * xs
        y = y.reshape(B, d_inner) * (z[:, t] / (1 + np.exp(-z[:, t])))
        y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + 1e-5) * p["norm_scale"]
        ys.append(y @ p["w_out"])
    return np.stack(ys, 1)


@pytest.mark.parametrize("chunk", [8, 16])
def test_mamba2_scan_matches_the_recurrence_with_conv_bias(chunk):
    cfg = ModelConfig(name="m", family="hybrid", n_layers=1, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=64, ssm_state=32,
                      ssm_n_groups=2, ssm_conv_bias=True)
    p = ssm.mamba2_params(KEY, cfg)
    ks = jax.random.split(jax.random.fold_in(KEY, 1), 5)
    nh = p["A_log"].shape[0]
    p.update(b_conv=0.3 * jax.random.normal(ks[0], p["b_conv"].shape),
             A_log=jnp.log(jax.random.uniform(ks[1], (nh,), minval=1.0, maxval=16.0)),
             dt_bias=jax.random.uniform(ks[2], (nh,), minval=-4.0, maxval=0.0),
             D=1.0 + 0.1 * jax.random.normal(ks[3], (nh,)))
    x = jax.random.normal(ks[4], (2, 37, 64))  # not a multiple of either chunk
    got = ssm.mamba2_forward(p, x, cfg, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), _recurrence(p, x, cfg),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("scale", [1.0 / 64, 0.3])
def test_flash_training_kernels_take_an_explicit_scale(scale):
    B, H, S, hd = 1, 2, 96, 16
    q, k, v, w = (jax.random.normal(jax.random.fold_in(KEY, i), (B, H, S, hd))
                  for i in range(4))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    def flash(q, k, v):
        return flash_attention_mha(q, k, v, scale=scale, block_q=32, block_k=32)

    def naive(q, k, v):
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        return t(naive_attention(t(q), t(k), t(v), causal=True, scale=scale))

    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(naive(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                    jax.grad(loss(naive), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
    default = flash_attention_mha(q, k, v, block_q=32, block_k=32)
    assert not np.allclose(np.asarray(default), np.asarray(flash(q, k, v)))
