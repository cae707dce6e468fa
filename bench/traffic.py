"""Traffic from a mix's parameters and a seed.

One general generator reads every mix file under ``bench/traffic/``. Sizes
and gaps are drawn at the stratified quantiles (i + 0.5) / n of their
distributions and put in an order drawn from the mix's fixed
``schedule_seed``: every run replays the same schedule, like a recorded
trace, so runs with different seeds do the same work. The run's seed draws
the contents: token ids uniform over the configuration's vocabulary
(training batches: Zipf with copied spans).

Distribution specs: ``{"dist": "lognormal", "median", "sigma", "min",
"max"}``, ``{"dist": "uniform", "min", "max"}`` (integers, both ends
included), ``{"dist": "exponential", "mean"}``, ``{"dist": "const",
"value"}``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# independent random streams drawn from one seed
STREAM_ORDER, STREAM_TOKENS, STREAM_WARMUP, STREAM_SAMPLE, STREAM_BATCH = range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    """The distribution's values at probabilities ``u`` (sorted in, sorted
    out)."""
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)
    if kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(np.int64)
    if kind == "exponential":
        return -spec["mean"] * np.log1p(-u)
    if kind == "const":
        return np.full(len(u), spec["value"])
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values at the stratified quantiles, in an order from ``rng``."""
    if n <= 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    return quantiles(spec, u)[rng.permutation(n)]


def _arrivals(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` Poisson arrival times from 0: exponential gaps of mean 1/rate,
    scaled so that they always sum to (n - 1) / rate."""
    if n <= 1:
        return np.zeros(max(n, 0))
    gaps = stratified({"dist": "exponential", "mean": 1.0 / rate}, n - 1, rng)
    gaps *= (n - 1) / rate / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)])


@dataclasses.dataclass
class Req:
    uid: int
    due_s: float            # seconds after the window opens (backlog: 0)
    prompt: np.ndarray      # int32 token ids
    max_new: int
    session: Optional[int] = None


def requests(mix: dict, vocab: int, seed: int, seconds: float) -> List[Req]:
    """Every request the mix sends in a window of ``seconds``, in due order:
    the schedule from the mix's ``schedule_seed``, the tokens from ``seed``."""
    order = rng_for(mix["schedule_seed"], STREAM_ORDER)
    toks = rng_for(seed, STREAM_TOKENS)
    kind = mix["arrivals"]
    out: List[Req] = []
    if kind in ("poisson", "backlog"):
        if kind == "poisson":
            n = int(math.floor(mix["rate_per_s"] * seconds))
            due = _arrivals(mix["rate_per_s"], n, order)
        else:
            n = int(mix["requests"])
            due = np.zeros(n)
        plen = stratified(mix["prompt_len"], n, order)
        olen = stratified(mix["output_len"], n, order)
        for i in range(n):
            out.append(Req(i, float(due[i]),
                           toks.integers(0, vocab, int(plen[i]), dtype=np.int32),
                           int(olen[i])))
    elif kind == "sessions":
        ns = int(math.floor(mix["session_rate_per_s"] * seconds))
        starts = _arrivals(mix["session_rate_per_s"], ns, order)
        doc = stratified(mix["doc_len"], ns, order)
        asks = stratified(mix["asks"], ns, order).astype(int)
        total = int(asks.sum())
        qlen = stratified(mix["question_len"], total, order)
        olen = stratified(mix["output_len"], total, order)
        think = stratified(mix["think_s"], total - ns, order)
        k = g = 0
        for s in range(ns):
            d = toks.integers(0, vocab, int(doc[s]), dtype=np.int32)
            t = float(starts[s])
            for a in range(int(asks[s])):
                if a:
                    t += float(think[g])
                    g += 1
                q = toks.integers(0, vocab, int(qlen[k]), dtype=np.int32)
                out.append(Req(0, t, np.concatenate([d, q]), int(olen[k]), s))
                k += 1
        out.sort(key=lambda r: r.due_s)
        for i, r in enumerate(out):
            r.uid = i
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    return [r for r in out if r.due_s < seconds]


def warmup_requests(mix: dict, vocab: int, seed: int, block: int,
                    max_seq: int) -> List[Req]:
    """Requests that compile every program the window uses and nothing
    else, meant to be submitted one a step: a prompt a little over two
    blocks long (the prefill chunk, a partial last chunk), then the same
    prompt again while the first is resident (a full prefix hit: the
    read-only chunk, and copy-on-write on its first decode), then a
    distinct one. Their tokens come from a stream the window never uses."""
    rng = rng_for(seed, STREAM_WARMUP)
    n = min(2 * block + 3, max_seq - 5)
    p = rng.integers(0, vocab, n, dtype=np.int32)
    q = rng.integers(0, vocab, n, dtype=np.int32)
    return [Req(-1, 0.0, p, 4), Req(-2, 0.0, p.copy(), 4), Req(-3, 0.0, q, 4)]


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> dict:
    """Step ``step``'s batch: Zipf(``zipf_a``) token ids, each row's first
    quarter copied into its second half (in-context signal), as the
    program's own ``make_batch_fn`` makes them. Every step and row differ."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed), STREAM_BATCH, int(step)]))
    b, s = mix["batch"], mix["seq"]
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks ** mix["zipf_a"]
    probs /= probs.sum()
    toks = rng.choice(vocab, size=(b, s), p=probs).astype(np.int32)
    w = s // 4
    toks[:, s // 2:s // 2 + w] = toks[:, :w]
    return {"tokens": toks}


def check_sample(finished_lengths: dict, n: int, seed: int) -> List[int]:
    """uids to compare with the reference: the longest finished request
    (prompt plus output) and ``n - 1`` others drawn from the seed."""
    if not finished_lengths:
        return []
    uids = sorted(finished_lengths)
    longest = max(uids, key=lambda u: (finished_lengths[u], u))
    rest = [u for u in uids if u != longest]
    rng = rng_for(seed, STREAM_SAMPLE)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + sorted(rest[i] for i in pick)
