"""Plain reference of the layer-pattern hybrid (granite-4.0-h): forward,
loss, gradients and Adam in straightforward ``jax.numpy``, with no kernel,
no chunked scan and no cache. It imports nothing of the program and reads
only the weights of :mod:`bench.hybrid.weights`.

Layer i is a pre-norm block: RMSNorm, its mixer (``layer_types[i]``:
Mamba2 or attention), the mixer's output times ``residual_multiplier``
added to the residual; then RMSNorm and the SwiGLU MLP, added the same way.
The token embeddings are multiplied by ``embedding_multiplier``; after the
final RMSNorm the tied head's logits are divided by ``logits_scaling``.

Mamba2: ``in_proj`` to z, x, B, C and dt (no bias); a depthwise causal
conv of width ``mamba_d_conv`` with bias over (x, B, C), then SiLU;
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the SSD in its
masked dual form

    y_t = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s + D x_t,
    cum_t = sum_{r <= t} dt_r A,

computed ``Q_BLOCK`` query rows at a time against every position (the mask
keeps s <= t). For each block, cum is taken relative to the block's first
row and summed outward from it, so it keeps its precision near the
diagonal at any length. Then the gated RMSNorm (``y * silu(z)`` normalised
over d_inner: one group) and ``out_proj``.

Attention: grouped-query, causal, no position embedding (NoPE), softmax
scale ``attention_multiplier``, ``Q_BLOCK`` query rows at a time.

Departures from the published model: none in the equations. The
vocabulary is the configuration file's slice; the head and the loss are
over it.

``dtype=float32, precision="highest"`` is the reference; ``dtype=bfloat16,
precision="default"`` is the control: the same arithmetic one precision
below what the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.hybrid import Dims

PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "default": jax.lax.Precision.DEFAULT}
Q_BLOCK = 128     # query rows per block of the SSD and of attention
HEAD_ROWS = 1024  # positions per block of the head and the loss


def _rows(n: int, most: int) -> int:
    """The largest block of at most ``most`` rows that divides ``n``."""
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


def _blocks(fn, n: int, rows: int):
    """``fn(first_row)`` -> (B, rows, ...) for each block of ``rows`` of
    ``n``, recomputed in the backward, joined along axis 1."""
    out = jax.lax.map(jax.checkpoint(fn), jnp.arange(n // rows) * rows)
    return jnp.moveaxis(out, 0, 1).reshape((out.shape[1], n) + out.shape[3:])


class Model:
    """The equations of one configuration file's top level."""

    def __init__(self, cfg: dict, dtype=jnp.float32, precision="highest"):
        self.m = Dims.from_config(cfg)
        self.dtype = jnp.dtype(dtype)
        self.prec = PRECISION[precision]

    # -- pieces ----------------------------------------------------------------
    def _mm(self, a, b):
        return jnp.matmul(a, b.astype(self.dtype), precision=self.prec)

    def _rms(self, x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.m.eps) \
            * scale.astype(self.dtype)

    def _ssd(self, x, Bm, Cm, dt, a):
        """The dual form. x: (B, S, nh, hd); Bm, Cm: (B, S, g, N); dt and
        a = dt * A: (B, S, nh)."""
        B, S, nh, hd = x.shape
        g = Bm.shape[2]
        q = _rows(S, Q_BLOCK)
        pos = jnp.arange(S)
        xg = x.reshape(B, S, g, nh // g, hd)

        def block(q0):
            after = (pos >= q0)[None, :, None]
            # cum_s - cum_{q0-1}: sum_{q0 <= r <= s} a_r from q0 on, and
            # -sum_{s < r < q0} a_r before it, each summed outward from q0
            ahead = jnp.cumsum(jnp.where(after, a, 0), axis=1)
            behind = jnp.flip(jnp.cumsum(jnp.flip(jnp.where(after, 0, a), 1), 1), 1)
            behind = jnp.concatenate([behind[:, 1:], jnp.zeros_like(behind[:, :1])], 1)
            rel = jnp.where(after, ahead, -behind).transpose(0, 2, 1)  # (B, nh, S)
            rel_t = jax.lax.dynamic_slice_in_dim(rel, q0, q, 2)
            causal = pos[None, :] <= (q0 + jnp.arange(q))[:, None]       # (q, S)
            decay = jnp.where(causal, rel_t[..., :, None] - rel[..., None, :], -jnp.inf)
            w = jnp.exp(decay) * dt.transpose(0, 2, 1)[:, :, None, :]    # (B, nh, q, S)
            c_t = jax.lax.dynamic_slice_in_dim(Cm, q0, q, 1)
            cb = jnp.einsum("btgn,bsgn->bgts", c_t, Bm, precision=self.prec)
            w = w.reshape(B, g, nh // g, q, S) * cb[:, :, None]
            y = jnp.einsum("bghts,bsghd->btghd", w, xg, precision=self.prec)
            return y.reshape(B, q, nh, hd)

        return _blocks(block, S, q)

    def _mamba(self, x, w):
        m, dt_ = self.m, self.dtype
        B, S, _ = x.shape
        di, nh, hd, gn = m.d_inner, m.ssm_heads, m.ssm_head_dim, m.groups * m.d_state
        zxbcdt = self._mm(x, w["in_proj"])
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:di + m.conv_channels]
        dt_raw = zxbcdt[..., di + m.conv_channels:]
        # causal depthwise conv: weight[W - 1] multiplies the current position
        conv = w["conv.weight"].astype(dt_)
        W = m.conv_width
        shifted = [jnp.pad(xbc, ((0, 0), (k, 0), (0, 0)))[:, :S] for k in range(W)]
        xbc = jax.nn.silu(sum(shifted[k] * conv[W - 1 - k] for k in range(W))
                          + w["conv.bias"].astype(dt_))
        xs = xbc[..., :di].reshape(B, S, nh, hd)
        Bm = xbc[..., di:di + gn].reshape(B, S, m.groups, m.d_state)
        Cm = xbc[..., di + gn:].reshape(B, S, m.groups, m.d_state)
        dt = jax.nn.softplus(dt_raw + w["dt_bias"].astype(dt_))
        a = dt * -jnp.exp(w["A_log"].astype(dt_))
        y = self._ssd(xs, Bm, Cm, dt, a) + xs * w["D"].astype(dt_)[:, None]
        y = y.reshape(B, S, di) * jax.nn.silu(z)
        return self._mm(self._rms(y, w["gate_norm.scale"]), w["out_proj"])

    def _attention(self, x, w):
        m = self.m
        B, S, _ = x.shape
        K, G, hd = m.kv_heads, m.heads // m.kv_heads, m.head_dim
        q = self._mm(x, w["wq"]).reshape(B, S, K, G, hd)
        k = self._mm(x, w["wk"]).reshape(B, S, K, hd)
        v = self._mm(x, w["wv"]).reshape(B, S, K, hd)
        rows = _rows(S, Q_BLOCK)
        pos = jnp.arange(S)

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, rows, 1)
            sc = jnp.einsum("bqkgh,bskh->bkgqs", qb, k,
                            precision=self.prec) * m.attn_scale
            mask = pos[None, :] <= (q0 + jnp.arange(rows))[:, None]
            p = jax.nn.softmax(jnp.where(mask, sc, jnp.asarray(-1e30, sc.dtype)), -1)
            out = jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision=self.prec)
            return out.reshape(B, rows, m.heads * hd)

        return self._mm(_blocks(block, S, rows), w["wo"])

    def _layer(self, h, w, kind: str):
        r = self.m.res_mult
        x = self._rms(h, w["in_norm.scale"])
        h = h + r * (self._mamba(x, w) if kind == "mamba" else self._attention(x, w))
        x = self._rms(h, w["mlp_norm.scale"])
        g = jax.nn.silu(self._mm(x, w["w_gate"]))
        return h + r * self._mm(g * self._mm(x, w["w_up"]), w["w_down"])

    # -- model -----------------------------------------------------------------
    def hidden(self, w, tokens):
        """Final-normed hidden states (B, S, d) of ``tokens`` (B, S); each
        layer is recomputed in the backward."""
        h = jnp.take(w["embed"], tokens, axis=0).astype(self.dtype) * self.m.emb_mult
        for i, (kind, _) in enumerate(self.m.runs()):
            pre = f"runs.{i}."
            run = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
            body = jax.checkpoint(
                lambda c, lw, kind=kind: (self._layer(c, lw, kind), None))
            h, _ = jax.lax.scan(body, h, run)
        return self._rms(h, w["final_norm.scale"])

    def loss(self, w, tokens):
        """Mean next-token cross-entropy over every predicted position, the
        head and the loss ``HEAD_ROWS`` positions at a time."""
        h = self.hidden(w, tokens)
        B, S, _ = h.shape
        gold = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
        rows = _rows(S, HEAD_ROWS)

        def block(r0):
            hb = jax.lax.dynamic_slice_in_dim(h, r0, rows, 1)
            lg = self._mm(hb, w["embed"].T) / self.m.logit_div
            gb = jax.lax.dynamic_slice_in_dim(gold, r0, rows, 1)
            return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, gb[..., None], -1)[..., 0]

        nll = _blocks(block, S, rows)[:, :-1]  # the last position predicts nothing
        return jnp.mean(nll)


def train(cfg: dict, w0: Dict[str, jax.Array], batches: Sequence[dict],
          *, dtype=jnp.float32, precision="highest") -> dict:
    """Adam steps over ``batches`` from ``w0`` (consumed). Returns each
    step's loss, each leaf's first-gradient norm and the final weights."""
    model = Model(cfg, dtype, precision)
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    sd = model.dtype

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, t, tokens):
        loss, g = jax.value_and_grad(model.loss)(w, tokens)
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in w}
        v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in w}
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t
        w = {k: (w[k] - lr * (m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + eps)
                 ).astype(sd) for k in w}
        norms = {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                 for k, x in g.items()}
        return w, m, v, loss, norms

    w = {k: a.astype(sd) for k, a in w0.items()}
    m = {k: jnp.zeros(a.shape, sd) for k, a in w.items()}
    v = {k: jnp.zeros(a.shape, sd) for k, a in w.items()}
    losses, gnorm = [], None
    with jax.default_matmul_precision(precision):
        for i, b in enumerate(batches):
            w, m, v, loss, gn = step(w, m, v, jnp.asarray(i + 1, sd),
                                     jnp.asarray(b["tokens"]))
            losses.append(float(loss))
            if gnorm is None:
                gnorm = {k: float(x) for k, x in gn.items()}
    del m, v
    return {"losses": losses, "grad_norms": gnorm, "weights": w}
