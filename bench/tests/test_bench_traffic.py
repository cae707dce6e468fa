"""The traffic generator: deterministic per seed, the same sizes for every
seed, and the distributions its mix files state."""
import json
import math

import numpy as np
import pytest

from bench import traffic
from bench.tests.conftest import REPO

BIG = 2**33 + 12345


def mix(name):
    return json.loads((REPO / "bench" / "traffic" / f"{name}.json").read_text())


def lens(reqs):
    return sorted(len(r.prompt) for r in reqs), sorted(r.max_new for r in reqs)


def test_same_seed_same_requests():
    a = traffic.requests(mix("chat"), 49155, BIG, 45)
    b = traffic.requests(mix("chat"), 49155, BIG, 45)
    assert [(r.due_s, r.max_new) for r in a] == [(r.due_s, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_replays_the_schedule_with_its_own_tokens():
    a = traffic.requests(mix("chat"), 49155, 1, 45)
    b = traffic.requests(mix("chat"), 49155, BIG, 45)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    other = dict(mix("chat"), schedule_seed=1)
    c = traffic.requests(other, 49155, 1, 45)
    assert lens(c) == lens(a)  # the same sizes in another order
    assert [r.max_new for r in c] != [r.max_new for r in a]


def test_poisson_chat_matches_its_distributions():
    m = mix("chat")
    reqs = traffic.requests(m, 49155, 7, 45)
    assert len(reqs) == math.floor(m["rate_per_s"] * 45)
    p, o = lens(reqs)
    assert min(p) >= 32 and max(p) <= 1024 and min(o) >= 16 and max(o) <= 512
    assert abs(np.median(p) - 256) <= 0.1 * 256
    assert abs(np.median(o) - 128) <= 0.1 * 128
    due = [r.due_s for r in reqs]
    assert due[0] == 0.0 and due == sorted(due)
    assert due[-1] == pytest.approx((len(reqs) - 1) / m["rate_per_s"])
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.2)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 49155 for r in reqs)


def test_stratified_lognormal_median_and_clip():
    spec = {"dist": "lognormal", "median": 2048, "sigma": 0.3, "min": 1024, "max": 3072}
    v = traffic.stratified(spec, 1001, np.random.default_rng(0))
    assert np.median(v) == 2048 and v.min() >= 1024 and v.max() <= 3072
    u = traffic.stratified({"dist": "uniform", "min": 4, "max": 6}, 300,
                           np.random.default_rng(0))
    assert sorted(set(u)) == [4, 5, 6] and list(np.bincount(u)[4:]) == [100, 100, 100]


def test_docqa_sessions_share_their_document():
    m = mix("docqa")
    reqs = traffic.requests(m, 32000, 3, 400)
    sessions = {}
    for r in reqs:
        sessions.setdefault(r.session, []).append(r)
    assert len(sessions) == math.floor(m["session_rate_per_s"] * 400)
    for asks in sessions.values():
        assert len(asks) <= 6
        for a in asks:
            assert 1024 + 32 <= len(a.prompt) <= 3072 + 128
            assert 16 <= a.max_new <= 64
            # every ask repeats the session's document (at least 1024 tokens)
            assert np.array_equal(a.prompt[:1024], asks[0].prompt[:1024])
        assert len({len(a.prompt) for a in asks}) > 1 or len(asks) == 1
    assert [r.uid for r in reqs] == list(range(len(reqs)))


def test_backlog_is_due_at_once_and_train_batches_differ():
    reqs = traffic.requests(mix("reason-backlog"), 49155, 5, 45)
    assert len(reqs) == 96 and {r.due_s for r in reqs} == {0.0}
    m = mix("train-2k")
    b0 = traffic.train_batch(m, 49155, BIG, 0)["tokens"]
    b1 = traffic.train_batch(m, 49155, BIG, 1)["tokens"]
    assert b0.shape == (2, 2048) and b0.dtype == np.int32
    assert not np.array_equal(b0[0], b0[1]) and not np.array_equal(b0, b1)
    assert np.array_equal(b0, traffic.train_batch(m, 49155, BIG, 0)["tokens"])
    assert np.array_equal(b0[:, 1024:1536], b0[:, :512])  # the copied span


def test_check_sample_keeps_the_longest():
    lengths = {u: 10 + u for u in range(30)}
    pick = traffic.check_sample(lengths, 6, BIG)
    assert pick[0] == 29 and len(set(pick)) == 6
    assert pick == traffic.check_sample(lengths, 6, BIG)
