"""Plain reference of a dense decoder: forward, loss, gradients and Adam in
straightforward ``jax.numpy``, with no kernel, cache or batching.

It follows the published layer equations as the configuration file states
them (RMSNorm or LayerNorm, rotary positions in the split-half convention,
grouped-query causal attention, SwiGLU or squared-ReLU MLP, tied or untied
head, and Granite's embedding, attention, residual and logit multipliers
where the file gives them). It imports nothing of the program and reads
only the weights of :mod:`bench.weights`.

``dtype=float32, precision="highest"`` is the reference; ``dtype=bfloat16,
precision="default"`` is the control: the same arithmetic one precision
below what the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "default": jax.lax.Precision.DEFAULT}
Q_BLOCK = 512     # attention query rows per block
SEQ_BUCKET = 512  # sequences are right-padded to a multiple of this
HEAD_ROWS = 256   # logits rows per block


class Model:
    """The equations of one configuration file's ``model`` section."""

    def __init__(self, cfg: dict, dtype=jnp.float32, precision="highest"):
        m = cfg["model"]
        self.m = m
        self.dtype = jnp.dtype(dtype)
        self.prec = PRECISION[precision]
        self.heads = m["num_attention_heads"]
        self.kv_heads = m["num_key_value_heads"]
        self.hd = m.get("head_dim") or m["hidden_size"] // self.heads
        self.eps = m.get("rms_norm_eps", m.get("layer_norm_eps", 1e-5))
        self.layernorm = m["norm"] == "layernorm"
        self.act = m["hidden_act"]
        self.tied = bool(m["tie_word_embeddings"])
        self.theta = float(m["rope_theta"])
        self.scale = float(m.get("attention_multiplier", 1.0 / math.sqrt(self.hd)))
        self.emb_mult = float(m.get("embedding_multiplier", 1.0))
        self.res_mult = float(m.get("residual_multiplier", 1.0))
        self.logit_div = float(m.get("logits_scaling", 1.0))

    # -- pieces ----------------------------------------------------------------
    def _mm(self, a, b):
        return jnp.matmul(a, b.astype(self.dtype), precision=self.prec)

    def _norm(self, x, w, prefix):
        scale = w[prefix + ".scale"].astype(self.dtype)
        if self.layernorm:
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + self.eps) * scale \
                + w[prefix + ".bias"].astype(self.dtype)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps) \
            * scale

    def _rope(self, x, pos):
        freqs = 1.0 / (self.theta ** (jnp.arange(0, self.hd, 2,
                                                 dtype=jnp.float32) / self.hd))
        ang = pos[:, None].astype(jnp.float32) * freqs
        cos = jnp.cos(ang)[None, :, None, :].astype(self.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(self.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _attention(self, q, k, v):
        """Causal grouped-query attention, ``Q_BLOCK`` query rows at a time.
        q: (B, S, H, hd); k, v: (B, S, K, hd)."""
        B, S, H, hd = q.shape
        K = self.kv_heads
        qg = q.reshape(B, S, K, H // K, hd)
        outs = []
        for s0 in range(0, S, Q_BLOCK):
            qb = qg[:, s0:s0 + Q_BLOCK]
            n = qb.shape[1]
            sc = jnp.einsum("bqkgh,bskh->bkgqs", qb, k,
                            precision=self.prec) * self.scale
            mask = jnp.arange(S)[None, :] <= (s0 + jnp.arange(n))[:, None]
            sc = jnp.where(mask, sc, jnp.asarray(-1e30, sc.dtype))
            p = jax.nn.softmax(sc, axis=-1)
            outs.append(jnp.einsum("bkgqs,bskh->bqkgh", p, v,
                                   precision=self.prec))
        return jnp.concatenate(outs, 1).reshape(B, S, H * hd)

    def _mlp(self, x, w):
        if self.act == "relu2":
            return self._mm(jnp.square(jax.nn.relu(self._mm(x, w["w_up"]))),
                            w["w_down"])
        if self.act != "silu":
            raise ValueError(f"activation {self.act!r}")
        g = jax.nn.silu(self._mm(x, w["w_gate"]))
        return self._mm(g * self._mm(x, w["w_up"]), w["w_down"])

    def _layer(self, h, w, pos):
        B, S, _ = h.shape
        x = self._norm(h, w, "attn_norm")
        q = self._mm(x, w["wq"]).reshape(B, S, self.heads, self.hd)
        k = self._mm(x, w["wk"]).reshape(B, S, self.kv_heads, self.hd)
        v = self._mm(x, w["wv"]).reshape(B, S, self.kv_heads, self.hd)
        a = self._attention(self._rope(q, pos), self._rope(k, pos), v)
        h = h + self.res_mult * self._mm(a, w["wo"])
        return h + self.res_mult * self._mlp(self._norm(h, w, "mlp_norm"), w)

    # -- model -----------------------------------------------------------------
    def hidden(self, w, tokens, *, remat: bool = False):
        """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
        S = tokens.shape[1]
        pos = jnp.arange(S)
        h = jnp.take(w["embed"], tokens, axis=0).astype(self.dtype) * self.emb_mult
        layers = {k[len("layers."):]: v for k, v in w.items()
                  if k.startswith("layers.")}
        body = lambda c, lw: (self._layer(c, lw, pos), None)  # noqa: E731
        if remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, layers)
        return self._norm(h, w, "final_norm")

    def logits(self, w, h):
        head = w["embed"].T if self.tied else w["lm_head"]
        return self._mm(h, head) / self.logit_div

    def loss(self, w, tokens):
        """Mean next-token cross-entropy over every predicted position."""
        h = self.hidden(w, tokens, remat=True)[:, :-1]
        lg = self.logits(w, h)
        gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def _tree_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


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
        return w, m, v, loss, _tree_norms(g)

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


def delta_norms(w1: Dict[str, jax.Array], w0: Dict[str, jax.Array]) -> Dict[str, float]:
    """Each leaf's norm of the change from ``w0`` to ``w1``."""
    f = jax.jit(lambda a, b: _tree_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    return {k: float(x) for k, x in f(w1, w0).items()}


def _bucket(n: int) -> int:
    return -(-n // SEQ_BUCKET) * SEQ_BUCKET


class Scorer:
    """Logits of served positions, one sequence at a time, at a few
    compiled lengths (multiples of ``SEQ_BUCKET``)."""

    def __init__(self, cfg: dict, dtype=jnp.float32, precision="highest"):
        self.model = Model(cfg, dtype, precision)
        self._hidden = jax.jit(lambda w, t: self.model.hidden(w, t)[0])
        self._logits = jax.jit(self.model.logits)

    def served_logits(self, w, prompt: np.ndarray, served: Sequence[int]):
        """(n, V) float32 logits at the positions that predicted each of the
        ``n`` served tokens (teacher-forced on the served stream)."""
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        P, n = len(prompt), len(served)
        padded = np.zeros(_bucket(len(seq)), np.int32)
        padded[:len(seq)] = seq
        with jax.default_matmul_precision(
                "highest" if self.model.prec == PRECISION["highest"] else "default"):
            h = self._hidden(w, jnp.asarray(padded)[None])
            rows = []
            for r0 in range(P - 1, P - 1 + n, HEAD_ROWS):
                idx = np.zeros(HEAD_ROWS, np.int32)
                cnt = min(HEAD_ROWS, P - 1 + n - r0)
                idx[:cnt] = np.arange(r0, r0 + cnt)
                lg = self._logits(w, h[jnp.asarray(idx)])
                rows.append(lg[:cnt].astype(jnp.float32))
        return jnp.concatenate(rows, 0)


def logit_gap(ref_logits, picked: Sequence[int]) -> Tuple[float, int]:
    """Widest gap by which a picked token's reference logit lies below the
    reference's best at its position, and how many picks were not the
    reference's best."""
    ref = np.asarray(ref_logits)
    picked = np.asarray(picked)
    best = ref.max(-1)
    got = ref[np.arange(len(picked)), picked]
    gap = best - got
    return float(gap.max()), int((gap > 0).sum())
