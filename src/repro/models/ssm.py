"""State-space blocks: Mamba2 (chunked SSD) and RWKV6 (data-dependent decay).

Both provide a full-sequence train/prefill path and an O(1)-state decode step,
which is what makes the ``long_500k`` cell sub-quadratic.

Mamba2 recurrence (scalar-per-head A, groups share B/C):
    h_t = exp(A * dt_t) h_{t-1} + dt_t * B_t x_t        y_t = C_t . h_t + D x_t
computed in chunks of L: intra-chunk quadratic form + inter-chunk state carry
(the SSD algorithm), so the HLO is matmul-dominated instead of a length-S loop.

RWKV6 recurrence (per-channel data-dependent decay w_t, bonus u):
    y_t = r_t . (S_{t-1} + diag(u) k_t^T v_t)           S_t = diag(w_t) S_{t-1} + k_t^T v_t
also computed in the chunked linear-attention form.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import obs
from repro.models.common import dense_init

# ---------------------------------------------------------------------------
# Mamba2
# ---------------------------------------------------------------------------

MAMBA_HEAD_DIM = 64
SSD_CHUNK = 256  # positions per chunk of the SSD scan (mamba_chunk_size)


def mamba2_dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    hd = MAMBA_HEAD_DIM
    nh = d_inner // hd
    g, N = cfg.ssm_n_groups, cfg.ssm_state
    conv_ch = d_inner + 2 * g * N
    return d_inner, hd, nh, g, N, conv_ch


def mamba2_params(key, cfg, dtype=jnp.float32):
    d_inner, hd, nh, g, N, conv_ch = mamba2_dims(cfg)
    d_in_proj = 2 * d_inner + 2 * g * N + nh
    ks = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(ks[0], cfg.d_model, d_in_proj, dtype),
        "w_conv": (jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_ch)) * 0.1).astype(dtype),
        "A_log": jnp.zeros((nh,), dtype),
        "dt_bias": jnp.full((nh,), math.log(math.e - 1), dtype),  # softplus^-1(1)
        "D": jnp.ones((nh,), dtype),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "w_out": dense_init(ks[2], d_inner, cfg.d_model, dtype),
    }
    if cfg.ssm_conv_bias:
        p["b_conv"] = jnp.zeros((conv_ch,), dtype)
    return p


def _causal_conv1d(u, w, b=None):
    """Depthwise causal conv. u: (B,S,C), w: (W,C), b: (C,) or None."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(u) if b is None else jnp.broadcast_to(b, u.shape)
    for i in range(W):
        out = out + pad[:, i:i + u.shape[1], :] * w[i]
    return out


def _mamba_project(p, x, cfg):
    d_inner, hd, nh, g, N, conv_ch = mamba2_dims(cfg)
    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:d_inner + conv_ch]
    dt = zxbcdt[..., d_inner + conv_ch:]
    return z, xBC, dt


def _mamba_split(xBC, cfg, B_, S):
    d_inner, hd, nh, g, N, _ = mamba2_dims(cfg)
    xs = xBC[..., :d_inner].reshape(B_, S, nh, hd)
    Bm = xBC[..., d_inner:d_inner + g * N].reshape(B_, S, g, N)
    Cm = xBC[..., d_inner + g * N:].reshape(B_, S, g, N)
    return xs, Bm, Cm


def _mamba_out(p, y, z, cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    B_, S = y.shape[:2]
    y = y.reshape(B_, S, d_inner) * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + 1e-5)
    y = (yf * p["norm_scale"].astype(jnp.float32)).astype(y.dtype)
    return y @ p["w_out"]


def mamba2_forward(p, x, cfg, chunk: int = SSD_CHUNK, return_state: bool = False):
    """Chunked SSD scan. x: (B,S,d) -> (B,S,d). Sets the
    ``ssd_chunks{chunk}`` gauge (chunks a call scans) when it traces."""
    B_, S, _ = x.shape
    d_inner, hd, nh, g, N, conv_ch = mamba2_dims(cfg)
    hpg = nh // g
    z, xBC_raw, dt_raw = _mamba_project(p, x, cfg)
    xBC = jax.nn.silu(_causal_conv1d(xBC_raw, p["w_conv"], p.get("b_conv")))
    xs, Bm, Cm = _mamba_split(xBC, cfg, B_, S)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    loga = dt * A[None, None, :]  # (B,S,nh), negative

    L = min(chunk, S)
    n_chunks = -(-S // L)
    obs.get_telemetry().metrics.gauge(
        "ssd_chunks", "chunks one Mamba2 call scans").labels(chunk=L).set(n_chunks)
    pad = n_chunks * L - S
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        loga = jnp.pad(loga, ((0, 0), (0, pad), (0, 0)))

    def to_chunks(a):
        r = a.reshape((B_, n_chunks, L) + a.shape[2:])
        return jnp.moveaxis(r, 1, 0)

    xs_c, Bm_c, Cm_c, dt_c, la_c = map(to_chunks, (xs, Bm, Cm, dt, loga))
    causal = jnp.tril(jnp.ones((L, L), bool))

    def chunk_step(h, xs_):
        # h: (B, g, hpg, N, hd) fp32 state at chunk start
        xb, Bb, Cb, dtb, lab = xs_
        xb = xb.astype(jnp.float32).reshape(B_, L, g, hpg, hd)
        Bb = Bb.astype(jnp.float32)
        Cb = Cb.astype(jnp.float32)
        cum = jnp.cumsum(lab, axis=1)  # (B,L,nh)
        cum_h = cum.reshape(B_, L, g, hpg)
        # intra-chunk: y_t += sum_{s<=t} (C_t.B_s) exp(cum_t-cum_s) dt_s x_s
        dots = jnp.einsum("btgn,bsgn->bgts", Cb, Bb)
        decay = cum[:, :, None, :] - cum[:, None, :, :]  # (B,t,s,nh)
        decay = jnp.where(causal[None, :, :, None], decay, -jnp.inf)
        w = jnp.exp(decay) * dtb[:, None, :, :]
        wg = w.reshape(B_, L, L, g, hpg)
        y_intra = jnp.einsum("bgts,btsgh,bsghd->btghd", dots, wg, xb)
        # inter-chunk: y_t += C_t . h * exp(cum_t)
        y_inter = jnp.einsum("btgn,bghnd,btgh->btghd", Cb, h, jnp.exp(cum_h))
        # state: h' = h*exp(cum_L) + sum_s exp(cum_L-cum_s) dt_s B_s x_s
        wlast = jnp.exp(cum[:, -1:, :] - cum) * dtb  # (B,L,nh)
        dstate = jnp.einsum("bsgn,bsgh,bsghd->bghnd",
                            Bb, wlast.reshape(B_, L, g, hpg), xb)
        h_new = h * jnp.exp(cum_h[:, -1])[..., None, None] + dstate
        y = (y_intra + y_inter).reshape(B_, L, nh, hd)
        return h_new, y

    h0 = jnp.zeros((B_, g, hpg, N, hd), jnp.float32)
    # checkpoint each chunk: backward recomputes the intra-chunk quadratics
    # instead of saving O(L^2) decay/score residuals per chunk
    h_fin, ys = jax.lax.scan(jax.checkpoint(chunk_step), h0,
                             (xs_c, Bm_c, Cm_c, dt_c, la_c))
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, n_chunks * L, nh, hd)[:, :S]
    y = y + xs[:, :S].astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    out = _mamba_out(p, y.astype(x.dtype), z, cfg)
    if return_state:
        W = cfg.ssm_conv_width
        conv_tail = xBC_raw[:, -(W - 1):] if S >= W - 1 else jnp.pad(
            xBC_raw, ((0, 0), (W - 1 - S, 0), (0, 0)))
        state = {"h": h_fin.reshape(B_, nh, N, hd), "conv": conv_tail}
        return out, state
    return out


def init_mamba_state(cfg, batch: int, dtype=jnp.float32):
    d_inner, hd, nh, g, N, conv_ch = mamba2_dims(cfg)
    return {
        "h": jnp.zeros((batch, nh, N, hd), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch), dtype),
    }


def mamba2_decode(p, x, state, cfg):
    """One-token step. x: (B,1,d)."""
    B_ = x.shape[0]
    d_inner, hd, nh, g, N, conv_ch = mamba2_dims(cfg)
    hpg = nh // g
    z, xBC_raw, dt_raw = _mamba_project(p, x, cfg)
    xBC_t = xBC_raw[:, 0]
    conv_buf = jnp.concatenate([state["conv"].astype(xBC_t.dtype), xBC_t[:, None]], axis=1)
    conv_out = jnp.einsum("bwc,wc->bc", conv_buf, p["w_conv"])
    if "b_conv" in p:
        conv_out = conv_out + p["b_conv"]
    xBC = jax.nn.silu(conv_out)[:, None]
    xs, Bm, Cm = _mamba_split(xBC, cfg, B_, 1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt * A[None])  # (B,nh)
    xs_f = xs[:, 0].astype(jnp.float32).reshape(B_, g, hpg, hd)
    Bf = Bm[:, 0].astype(jnp.float32)
    Cf = Cm[:, 0].astype(jnp.float32)
    h = state["h"].reshape(B_, g, hpg, N, hd)
    dstate = jnp.einsum("bgn,bgh,bghd->bghnd", Bf, dt.reshape(B_, g, hpg), xs_f)
    h = h * a.reshape(B_, g, hpg)[..., None, None] + dstate
    y = jnp.einsum("bgn,bghnd->bghd", Cf, h).reshape(B_, 1, nh, hd)
    y = y + xs.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    new_state = {"h": h.reshape(B_, nh, N, hd), "conv": conv_buf[:, 1:]}
    return _mamba_out(p, y.astype(x.dtype), z, cfg), new_state


# ---------------------------------------------------------------------------
# RWKV6 (Finch)
# ---------------------------------------------------------------------------

_DECAY_RANK = 64


def rwkv6_params(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    H, hd = cfg.n_heads, cfg.head_dim
    ks = jax.random.split(key, 11)
    return {
        "tmix": {
            "w_r": dense_init(ks[0], d, d, dtype),
            "w_k": dense_init(ks[1], d, d, dtype),
            "w_v": dense_init(ks[2], d, d, dtype),
            "w_g": dense_init(ks[3], d, d, dtype),
            "w_o": dense_init(ks[4], d, d, dtype),
            "w_decay_a": dense_init(ks[5], d, _DECAY_RANK, dtype),
            "w_decay_b": dense_init(ks[6], _DECAY_RANK, d, dtype),
            "decay_base": jnp.full((d,), -6.0, dtype),
            "u": (jax.random.normal(ks[7], (H, hd)) * 0.1).astype(dtype),
            "mix": jnp.full((5, d), 0.5, dtype),  # r,k,v,g,w token-shift coefs
            "ln_scale": jnp.ones((d,), dtype),
        },
        "cmix": {
            "w_kc": dense_init(ks[8], d, cfg.d_ff, dtype),
            "w_vc": dense_init(ks[9], cfg.d_ff, d, dtype),
            "w_rc": dense_init(ks[10], d, d, dtype),
            "mix": jnp.full((2, d), 0.5, dtype),
        },
    }


def _token_shift(x, prev=None):
    """Shift right by one along seq; ``prev`` supplies position -1 for decode."""
    if prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x.shape[1] == 1:
        return prev[:, None]
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def _rwkv_tmix_inputs(p, x, xx, cfg):
    H, hd = cfg.n_heads, cfg.head_dim
    B_, S, d = x.shape
    mix = p["mix"]
    mx = [x + (xx - x) * mix[i] for i in range(5)]
    r = (mx[0] @ p["w_r"]).reshape(B_, S, H, hd)
    k = (mx[1] @ p["w_k"]).reshape(B_, S, H, hd)
    v = (mx[2] @ p["w_v"]).reshape(B_, S, H, hd)
    g = mx[3] @ p["w_g"]
    wraw = p["decay_base"].astype(jnp.float32) + (
        jnp.tanh(mx[4] @ p["w_decay_a"]) @ p["w_decay_b"]).astype(jnp.float32)
    logw = -jnp.exp(wraw)  # (B,S,d) log-decay, negative
    return r, k, v, g, logw.reshape(B_, S, H, hd)


def _rwkv_out(p, y, g, cfg):
    B_, S = y.shape[:2]
    H, hd = cfg.n_heads, cfg.head_dim
    yf = y.astype(jnp.float32)  # per-head groupnorm
    mu = yf.mean(-1, keepdims=True)
    var = ((yf - mu) ** 2).mean(-1, keepdims=True)
    yf = (yf - mu) * jax.lax.rsqrt(var + 1e-5)
    yf = yf.reshape(B_, S, H * hd) * p["ln_scale"].astype(jnp.float32)
    return (yf.astype(g.dtype) * jax.nn.silu(g)) @ p["w_o"]


def rwkv6_tmix(p, x, cfg, state=None, chunk: int = 128, return_state: bool = False):
    """Full-sequence WKV, chunked linear-attention form. x: (B,S,d)."""
    B_, S, d = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    xx = _token_shift(x)
    r, k, v, g, logw = _rwkv_tmix_inputs(p, x, xx, cfg)
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    u = p["u"].astype(jnp.float32)

    L = min(chunk, S)
    n_chunks = -(-S // L)
    pad = n_chunks * L - S
    if pad:
        rf, kf, vf = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (rf, kf, vf))
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def to_chunks(a):
        return jnp.moveaxis(a.reshape(B_, n_chunks, L, H, hd), 1, 0)

    rc, kc, vc, wc = map(to_chunks, (rf, kf, vf, logw))
    strict_causal = jnp.tril(jnp.ones((L, L), bool), k=-1)

    def step(Sstate, xs_):
        rb, kb, vb, lw = xs_  # (B,L,H,hd)
        cum = jnp.cumsum(lw, axis=1)
        cum_excl = cum - lw  # sum_{i<=t-1}
        r_dec = rb * jnp.exp(cum_excl)
        k_dec = kb * jnp.exp(-cum)
        # intra: scores[t,s] = sum_c r_t k_s exp(cum_{t-1}-cum_s), s<t; diag via u
        scores = jnp.einsum("blhk,bmhk->bhlm", r_dec, k_dec)
        scores = jnp.where(strict_causal[None, None], scores, 0.0)
        diag = jnp.einsum("blhk,blhk->blh", rb, kb * u[None, None])
        y_intra = jnp.einsum("bhlm,bmhv->blhv", scores, vb) + diag[..., None] * vb
        # inter: y_t += r_t exp(cum_{t-1}) . S
        y_inter = jnp.einsum("blhk,bhkv->blhv", r_dec, Sstate)
        # state: S' = diag(exp(cum_L)) S + sum_s exp(cum_L-cum_s) k_s v_s
        wlast = jnp.exp(cum[:, -1][:, None] - cum)
        S_new = Sstate * jnp.exp(cum[:, -1])[..., None] + jnp.einsum(
            "blhk,blhv->bhkv", kb * wlast, vb)
        return S_new, y_inter + y_intra

    S0 = state if state is not None else jnp.zeros((B_, H, hd, hd), jnp.float32)
    S_fin, ys = jax.lax.scan(jax.checkpoint(step), S0, (rc, kc, vc, wc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, n_chunks * L, H, hd)[:, :S]
    out = _rwkv_out(p, y.astype(x.dtype), g, cfg)
    if return_state:
        return out, S_fin, x[:, -1]
    return out


def init_rwkv_state(cfg, batch: int, dtype=jnp.float32):
    H, hd = cfg.n_heads, cfg.head_dim
    return {
        "S": jnp.zeros((batch, H, hd, hd), jnp.float32),
        "prev_t": jnp.zeros((batch, cfg.d_model), dtype),
        "prev_c": jnp.zeros((batch, cfg.d_model), dtype),
    }


def rwkv6_tmix_step(p, x, state, prev_x, cfg):
    """One-token decode. x: (B,1,d); state: (B,H,hd,hd)."""
    H, hd = cfg.n_heads, cfg.head_dim
    xx = _token_shift(x, prev=prev_x)
    r, k, v, g, logw = _rwkv_tmix_inputs(p, x, xx, cfg)
    rf, kf, vf = (a[:, 0].astype(jnp.float32) for a in (r, k, v))
    wf = jnp.exp(logw[:, 0])
    u = p["u"].astype(jnp.float32)
    kv = jnp.einsum("bhk,bhv->bhkv", kf, vf)
    y = jnp.einsum("bhk,bhkv->bhv", rf, state + u[None, :, :, None] * kv)
    S_new = state * wf[..., None] + kv
    out = _rwkv_out(p, y[:, None].astype(x.dtype), g, cfg)
    return out, S_new, x[:, 0]


def rwkv6_cmix(p, x, prev=None):
    xx = _token_shift(x, prev=prev)
    mix = p["mix"]
    xk = x + (xx - x) * mix[0]
    xr = x + (xx - x) * mix[1]
    kk = jax.nn.relu(xk @ p["w_kc"])
    kk = kk * kk
    return jax.nn.sigmoid(xr @ p["w_rc"]) * (kk @ p["w_vc"]), x[:, -1]
