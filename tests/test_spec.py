"""Speculative decoding: verify kernels, verifiers, draft sources, engine.

The invariant everything here defends: speculation changes *how fast*
tokens come out, never *which* tokens. Greedy speculative decode must be
token-identical to one-token greedy decode — per layout (contig/paged),
per attention impl (naive/pallas), and across draft-depth changes
mid-stream (the serving rung the arbiter walks).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_decode_spec, flash_decode_spec_paged
from repro.launch.serve import ContinuousBatchingEngine, Request
from repro.models.registry import build_model
from repro.spec.draft import ModelDraft, NGramDraft, build_draft_source
from repro.spec.verify import greedy_verify, rejection_verify

TINY = ModelConfig(name="spec-tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                   tie_embeddings=True, source="tests/test_spec.py")
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# multi-token verify kernels vs a per-row naive reference
# ---------------------------------------------------------------------------


def _reference(q, k, v, lengths):
    """Per-(batch, draft-row) softmax attention over the causal window:
    row qi of sequence b attends to kv[:lengths[b] + qi + 1]."""
    B, K, S, G, hd = q.shape
    out = np.zeros(q.shape[:4] + (v.shape[-1],), np.float32)
    for b in range(B):
        for kh in range(K):
            for qi in range(S):
                n = int(lengths[b]) + qi + 1
                kk, vv = k[b, kh, :n], v[b, kh, :n]
                s = (q[b, kh, qi] / np.sqrt(hd)) @ kk.T
                p = np.exp(s - s.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                out[b, kh, qi] = p @ vv
    return out


def _spec_inputs(seed=0, B=3, K=2, S=3, G=2, hd=64, Smax=160):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, K, S, G, hd)).astype(np.float32)
    k = rng.standard_normal((Smax, B, K, hd)).astype(np.float32)
    k = np.ascontiguousarray(np.moveaxis(k, 0, 2))  # (B, K, Smax, hd)
    v = rng.standard_normal((B, K, Smax, hd)).astype(np.float32)
    lengths = np.array([5, 63, Smax - S], np.int32)  # edge: last tile full
    return q, k, v, lengths


def test_flash_decode_spec_matches_reference():
    q, k, v, lengths = _spec_inputs()
    got = np.asarray(flash_decode_spec(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), jnp.asarray(lengths),
                                       block_k=32))
    want = _reference(q, k, v, lengths)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_decode_spec_paged_matches_reference():
    q, k, v, lengths = _spec_inputs()
    B, K, Smax, hd = k.shape
    bs = 32
    T = Smax // bs
    # scatter each sequence's blocks into a shuffled physical pool
    rng = np.random.default_rng(1)
    phys = rng.permutation(B * T)
    table = phys.reshape(B, T).astype(np.int32)
    k_pool = np.zeros((B * T, K, bs, hd), np.float32)
    v_pool = np.zeros((B * T, K, bs, hd), np.float32)
    for b in range(B):
        for t in range(T):
            k_pool[table[b, t]] = k[b, :, t * bs:(t + 1) * bs]
            v_pool[table[b, t]] = v[b, :, t * bs:(t + 1) * bs]
    got = np.asarray(flash_decode_spec_paged(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table), jnp.asarray(lengths)))
    want = _reference(q, k, v, lengths)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# verifiers
# ---------------------------------------------------------------------------


def test_greedy_verify_equals_sequential_argmax():
    rng = np.random.default_rng(3)
    for _ in range(20):
        S, V = int(rng.integers(1, 5)), 16
        logits = rng.standard_normal((2, S, V)).astype(np.float32)
        drafts = rng.integers(0, V, (2, S - 1)).astype(np.int32)
        toks, n_emit = jax.device_get(
            greedy_verify(jnp.asarray(logits), jnp.asarray(drafts)))
        for b in range(2):
            best = logits[b].argmax(-1)
            want = []
            for i in range(S):
                want.append(int(best[i]))
                if i < S - 1 and drafts[b, i] != best[i]:
                    break
            assert list(toks[b, :n_emit[b]]) == want


def test_greedy_verify_full_acceptance_and_bonus():
    logits = np.full((1, 3, 8), -5.0, np.float32)
    logits[0, 0, 2] = logits[0, 1, 4] = logits[0, 2, 7] = 5.0
    toks, n = jax.device_get(greedy_verify(
        jnp.asarray(logits), jnp.asarray([[2, 4]], np.int32)))
    assert int(n[0]) == 3 and list(toks[0]) == [2, 4, 7]


def test_rejection_verify_accepts_certain_drafts():
    """One-hot proposals whose tokens carry ~all target mass: every draft
    accepted, bonus appended, emission count is the full window."""
    V, S = 8, 4
    logits = np.full((1, S, V), -20.0, np.float32)
    want = [1, 5, 3, 6]
    for i, t in enumerate(want):
        logits[0, i, t] = 20.0
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(9), i))(
        jnp.arange(S))[None]
    toks, n = jax.device_get(rejection_verify(
        jnp.asarray(logits), jnp.asarray([want[:-1]], np.int32), None, keys,
        temperature=0.7))
    assert int(n[0]) == S and list(toks[0]) == want


def test_rejection_verify_rejects_impossible_drafts():
    """A draft with zero target mass must be rejected and resampled from
    the (renormalized) residual = target distribution."""
    V = 8
    logits = np.full((1, 2, V), -jnp.inf, np.float32)
    logits[0, :, 3] = 0.0  # target mass entirely on token 3
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(4), i))(
        jnp.arange(2))[None]
    toks, n = jax.device_get(rejection_verify(
        jnp.asarray(logits), jnp.asarray([[5]], np.int32), None, keys,
        temperature=1.0))
    assert int(n[0]) == 1 and int(toks[0, 0]) == 3


# ---------------------------------------------------------------------------
# draft sources
# ---------------------------------------------------------------------------


def test_ngram_draft_rides_cycles():
    d = NGramDraft(max_n=3)
    d.admit(0, [7, 8, 9, 7, 8])
    drafts, probs = d.propose([0], 5)
    assert probs is None
    assert list(drafts[0]) == [9, 7, 8, 9, 7]  # chains through the window


def test_ngram_draft_most_recent_wins_and_release():
    d = NGramDraft(max_n=2)
    d.admit(1, [1, 2, 5, 1, 2, 9])  # context (1,2) -> 5 then -> 9
    drafts, _ = d.propose([1], 1)
    assert int(drafts[0, 0]) == 9
    d.release(1)
    drafts, _ = d.propose([1], 2)  # unknown slot: cold-start fallback
    assert drafts.shape == (1, 2)


def test_model_draft_rollback_bookkeeping():
    model = build_model(TINY, impl="naive")
    params = model.init(KEY)
    d = ModelDraft(model, params, max_batch=2, max_seq=32)
    d.admit(0, [3, 4, 5])
    drafts, probs = d.propose([0], 3)
    assert probs is None and drafts.shape == (1, 3)
    assert int(d.cache_len[0]) == 5  # 3 prompt + 2 ingested proposals
    d.commit(0, [int(drafts[0, 0])], 99)  # 1 accepted, rollback the rest
    assert int(d.cache_len[0]) == 4  # base 3 + 1 accepted
    assert d._pending[0] == [99]
    d2, _ = d.propose([0], 2)
    assert int(d.cache_len[0]) == 6  # caught up to 5, ingested 1 proposal


def test_build_draft_source_rejects_unknown():
    with pytest.raises(ValueError, match="unknown draft source"):
        build_draft_source("no-such-arch")


# ---------------------------------------------------------------------------
# engine: greedy speculative decode is token-identical
# ---------------------------------------------------------------------------


def _requests(n=8, seed=0, gen=12):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, TINY.vocab_size,
                                        int(rng.integers(3, 14))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, gen + 1)))
            for i in range(n)]


def _run(model, params, reqs, **kw):
    engine = ContinuousBatchingEngine(model, params, max_batch=3, max_seq=64,
                                      **kw)
    fin = engine.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                      for r in reqs])
    return {u: f.tokens for u, f in fin.items()}, engine


@pytest.fixture(scope="module")
def tiny_model():
    model = build_model(TINY, impl="naive")
    return model, model.init(KEY)


@pytest.fixture(scope="module")
def greedy_baseline(tiny_model):
    model, params = tiny_model
    return _run(model, params, _requests())[0]


@pytest.mark.parametrize("layout", ["contig", "paged"])
@pytest.mark.parametrize("depth", [1, 3])
def test_engine_greedy_token_identity(tiny_model, greedy_baseline, layout,
                                      depth):
    model, params = tiny_model
    got, engine = _run(model, params, _requests(), kv_layout=layout,
                       draft_depth=depth)
    assert got == greedy_baseline
    assert engine.spec_rounds > 0 and engine.spec_accepted >= 0
    assert engine.decode_steps <= \
        sum(len(t) for t in greedy_baseline.values())


@pytest.mark.parametrize("layout", ["contig", "paged"])
def test_engine_greedy_token_identity_pallas(greedy_baseline, layout):
    model = build_model(TINY, impl="pallas")
    params = model.init(KEY)
    got, _ = _run(model, params, _requests(), kv_layout=layout,
                  draft_depth=2)
    assert got == greedy_baseline


def test_engine_model_draft_token_identity(tiny_model, greedy_baseline):
    model, params = tiny_model
    draft_model = build_model(
        dataclasses.replace(TINY, name="spec-draft", n_layers=1, d_ff=64),
        impl="naive")
    draft = ModelDraft(draft_model, draft_model.init(jax.random.PRNGKey(5)),
                       max_batch=3, max_seq=64)
    got, engine = _run(model, params, _requests(), draft_depth=2,
                       draft_source=draft)
    assert got == greedy_baseline


def test_engine_spec_sampled_respects_budgets(tiny_model):
    """Sampled speculative serving: right token counts per request and a
    live acceptance counter (distribution faithfulness is the hypothesis
    property in test_property.py)."""
    model, params = tiny_model
    reqs = _requests(6, seed=2)
    got, engine = _run(model, params, reqs, draft_depth=3, temperature=0.8,
                       top_k=32)
    assert {u: len(t) for u, t in got.items()} == \
        {r.uid: r.max_new_tokens for r in reqs}
    assert engine.spec_drafted > 0


def test_set_draft_depth_mid_stream_keeps_identity(tiny_model,
                                                   greedy_baseline):
    """Walking the draft-depth rung mid-stream (the arbiter's move) never
    changes emitted tokens — only how many verify rounds they take."""
    model, params = tiny_model
    engine = ContinuousBatchingEngine(model, params, max_batch=3, max_seq=64,
                                      draft_depth=4)
    for r in _requests():
        engine.submit(Request(r.uid, r.prompt.copy(), r.max_new_tokens))
    depths = [4, 2, 0, 3, 1]
    i = 0
    while engine.has_work:
        engine.set_draft_depth(depths[i % len(depths)])
        engine.step()
        i += 1
    assert {u: f.tokens for u, f in engine.finished.items()} == \
        greedy_baseline
    engine.set_draft_depth(None)  # rung restore: back to as-built depth
    assert engine.draft_depth == 4


def test_late_enable_draft_depth_builds_ngram_source(tiny_model):
    model, params = tiny_model
    engine = ContinuousBatchingEngine(model, params, max_batch=2, max_seq=64)
    assert engine.draft is None
    for r in _requests(4, seed=3):
        engine.submit(Request(r.uid, r.prompt.copy(), r.max_new_tokens))
    for _ in range(3):
        engine.step()
    engine.set_draft_depth(3)  # arbiter walks speculation *up* later
    assert engine.draft is not None
    while engine.has_work:
        engine.step()
    assert engine.spec_rounds > 0


def test_spec_stats_surface(tiny_model):
    model, params = tiny_model
    _, engine = _run(model, params, _requests(4, seed=4), draft_depth=2)
    st = engine.stats()
    assert st["draft_depth"] == 2
    assert st["spec_drafted"] >= st["spec_accepted"] >= 0
    assert 0.0 <= st["spec_acceptance"] <= 1.0
