"""Paged KV cache: BlockPool edge cases, block-table kernel parity against
the contiguous decode path, and the serve engine under the paged layout.

The invariant throughout: paging is *bookkeeping*, never math — every paged
result must match the contiguous cache the block table describes, token for
token, including ragged per-slot lengths and idle (retired) slots."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED
from repro.kernels.flash_attention import flash_decode, flash_decode_paged
from repro.launch.serve import ContinuousBatchingEngine, Request
from repro.models import build_model
from repro.paging import (BlockPool, BlockPoolExhausted, PagedKVCache,
                          gather_paged_kv)

KEY = jax.random.PRNGKey(0)


def _make(arch="llama3.2-1b", impl="naive"):
    cfg = ASSIGNED[arch].reduced()
    kw = {"moe_cf": 100.0} if arch == "deepseek-v3-671b" else {}
    model = build_model(cfg, impl=impl, **kw)
    params = model.init(KEY)
    return cfg, model, params


# ---------------------------------------------------------------------------
# BlockPool
# ---------------------------------------------------------------------------


def test_block_pool_exhaustion_raises():
    pool = BlockPool(num_blocks=4, block_size=8)  # 3 usable (block 0 null)
    pool.allocate("a", 16)  # 2 blocks
    assert pool.num_free == 1 and pool.can_allocate(8)
    assert not pool.can_allocate(9)
    with pytest.raises(BlockPoolExhausted):
        pool.allocate("b", 9)
    # the failed allocation corrupted nothing
    assert pool.num_free == 1 and pool.block_table("a") != []
    pool.allocate("b", 8)
    with pytest.raises(BlockPoolExhausted):
        pool.append_token("b", 8)  # boundary append with an empty pool


def test_block_pool_never_hands_out_null_block():
    pool = BlockPool(num_blocks=5, block_size=4)
    blocks = pool.allocate("a", 16)
    assert len(blocks) == 4 and BlockPool.NULL_BLOCK not in blocks


def test_block_pool_free_then_realloc_reuses_blocks():
    pool = BlockPool(num_blocks=6, block_size=4)
    a = pool.allocate("a", 12)
    pool.allocate("b", 8)
    freed = pool.free("a")
    assert freed == len(a) and pool.num_free == 3
    c = pool.allocate("c", 12)
    assert sorted(c) == sorted(a)  # freed blocks are the ones reused
    with pytest.raises(ValueError):
        pool.allocate("c", 4)  # double-allocate a live sequence id


def test_block_pool_append_on_boundary_only():
    pool = BlockPool(num_blocks=8, block_size=4)
    pool.allocate("a", 6)  # blocks for positions 0..7
    assert pool.append_token("a", 6) is None  # inside an owned block
    assert pool.append_token("a", 7) is None
    blk = pool.append_token("a", 8)  # first position of block 2
    assert blk is not None and pool.owned_blocks("a") == 3
    with pytest.raises(ValueError):
        pool.append_token("a", 20)  # skipping blocks is a bug, not an alloc


def test_block_pool_fragmentation_and_utilization_stats():
    pool = BlockPool(num_blocks=9, block_size=4)  # 8 usable
    pool.allocate("a", 5)  # 2 blocks, 8 slots
    pool.allocate("b", 4)  # 1 block, 4 slots
    assert pool.utilization() == pytest.approx(3 / 8)
    # live: a=5 of 8, b=4 of 4 -> 9 of 12 slots live
    assert pool.fragmentation({"a": 5, "b": 4}) == pytest.approx(1 - 9 / 12)
    assert pool.fragmentation({"a": 8, "b": 4}) == 0.0
    st = pool.stats({"a": 5, "b": 4})
    assert st["blocks_in_use"] == 3 and st["peak_blocks_in_use"] == 3
    pool.free("b")
    assert pool.stats()["peak_blocks_in_use"] == 3  # high-water mark sticks
    assert pool.fragmentation({"a": 5}) == pytest.approx(1 - 5 / 8)
    assert pool.fragmentation({}) == 1.0  # nothing live: all slots wasted


def test_paged_kv_cache_slot_rows_reset_to_null():
    kv = PagedKVCache(num_blocks=9, block_size=4, max_batch=2,
                      max_blocks_per_seq=3)
    blocks = kv.admit(0, "a", 6)
    assert list(kv.tables[0, :2]) == blocks and kv.tables[1].sum() == 0
    kv.append(0, 8)  # boundary: position 8 opens logical block 2
    assert kv.tables[0, 2] != 0 and kv.pool.owned_blocks("a") == 3
    with pytest.raises(ValueError, match="table width"):
        kv.append(0, 12)
    kv.release(0)
    assert kv.tables[0].sum() == 0  # idle slot writes land in the null block
    assert kv.pool.num_free == 8


# ---------------------------------------------------------------------------
# kernel parity: flash_decode_paged == flash_decode on the described cache
# ---------------------------------------------------------------------------


def _paged_from_contig(rng, k, v, bs):
    """Scatter a contiguous head-major (B, K, Smax, hd) cache into a block
    pool (NB, K, bs, hd) with a random (non-identity) block assignment;
    returns (k_pool, v_pool, table)."""
    B, K, Smax = k.shape[:3]
    T = Smax // bs
    NB = B * T + 1
    table = rng.permutation(np.arange(1, NB))[:B * T].reshape(B, T).astype(np.int32)
    k_pool = np.zeros((NB, K, bs, k.shape[-1]), k.dtype)
    v_pool = np.zeros((NB, K, bs, v.shape[-1]), v.dtype)
    for b in range(B):
        for t in range(T):
            k_pool[table[b, t]] = k[b, :, t * bs:(t + 1) * bs]
            v_pool[table[b, t]] = v[b, :, t * bs:(t + 1) * bs]
    return k_pool, v_pool, table


@pytest.mark.parametrize("geom", ["gqa", "mla"])
@pytest.mark.parametrize("ragged", [False, True])
def test_flash_decode_paged_matches_contiguous(geom, ragged):
    """Same math through the block table, GQA (K>1) and MLA-shaped (K=1,
    G=H, hdv != hd) geometries, scalar and ragged lengths incl. an idle
    (length 0) slot."""
    rng = np.random.default_rng(0)
    B, bs, T = 3, 8, 4
    Smax = bs * T
    if geom == "gqa":
        K, G, hd, hdv = 2, 3, 32, 32
        scale = None
    else:  # MLA decodes in latent space: one shared head, asymmetric dims
        K, G, hd, hdv = 1, 4, 48, 40
        scale = 0.125
    q = rng.standard_normal((B, K, G, hd)).astype(np.float32)
    k = rng.standard_normal((B, K, Smax, hd)).astype(np.float32)
    v = rng.standard_normal((B, K, Smax, hdv)).astype(np.float32)
    k_pool, v_pool, table = _paged_from_contig(rng, k, v, bs)
    lengths = np.asarray([Smax, 13, 0], np.int32) if ragged \
        else np.full((B,), 21, np.int32)

    kw = {} if scale is None else {"scale": scale}
    ref = flash_decode(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       jnp.asarray(lengths), **kw)
    out = flash_decode_paged(jnp.asarray(q), jnp.asarray(k_pool),
                             jnp.asarray(v_pool), jnp.asarray(table),
                             jnp.asarray(lengths), **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    if ragged:  # the idle slot must produce exact zeros, not NaNs
        assert np.all(np.asarray(out)[2] == 0.0)


def test_gather_paged_kv_reconstructs_contiguous():
    rng = np.random.default_rng(1)
    k = rng.standard_normal((2, 2, 16, 8)).astype(np.float32)
    k_pool, _, table = _paged_from_contig(rng, k, k, 4)
    back = gather_paged_kv(jnp.asarray(k_pool), jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(back), k)


# ---------------------------------------------------------------------------
# engine: paged layout is bookkeeping, never math
# ---------------------------------------------------------------------------


def _run_stream(model, params, *, layout, impl_reqs, max_batch=2, max_seq=32,
                **kw):
    engine = ContinuousBatchingEngine(model, params, max_batch=max_batch,
                                      max_seq=max_seq, kv_layout=layout, **kw)
    finished = engine.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                           for r in impl_reqs])
    return engine, {u: f.tokens for u, f in finished.items()}


def _ragged_reqs(seed=1, n=3):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, 64, 4 + 3 * i).astype(np.int32),
                    max_new_tokens=3 + i) for i in range(n)]


def test_engine_paged_token_identical_to_contig():
    """Queueing, mid-stream retirement, freed-slot admission and ragged
    lengths through the paged cache == the contiguous slabs, token for
    token. block_size 4 forces mid-decode boundary allocations."""
    _, model, params = _make()
    reqs = _ragged_reqs()
    _, contig = _run_stream(model, params, layout="contig", impl_reqs=reqs)
    engine, paged = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                                block_size=4)
    assert paged == contig
    st = engine.stats()
    assert st["pool"]["blocks_in_use"] == 0  # all retired -> all freed
    assert st["pool"]["peak_blocks_in_use"] > 0
    assert st["peak_kv_bytes"] < st["kv_bytes"] or st["kv_bytes"] == 0


def test_engine_paged_pallas_token_identical():
    """The paged flash-decode kernel serves the same stream as the paged
    naive gather oracle."""
    outs = {}
    for impl in ("naive", "pallas"):
        _, model, params = _make(impl=impl)
        _, outs[impl] = _run_stream(model, params, layout="paged",
                                    impl_reqs=_ragged_reqs(3), block_size=4)
    assert outs["naive"] == outs["pallas"]


def test_engine_paged_mla_token_identical_to_contig():
    """MLA (latent-space) paged decode parity on the deepseek geometry."""
    _, model, params = _make("deepseek-v3-671b")
    reqs = _ragged_reqs(5, n=3)
    _, contig = _run_stream(model, params, layout="contig", impl_reqs=reqs,
                            max_seq=24)
    _, paged = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                           max_seq=24, block_size=4)
    assert paged == contig


def test_engine_paged_admission_waits_for_pool_capacity():
    """With a pool too small for two residents, the second request queues
    (admission rejects, nothing corrupts) and is served after the first
    retires — both streams still match the roomy-pool run."""
    _, model, params = _make()
    reqs = [Request(uid=i, prompt=np.arange(2, 10, dtype=np.int32),
                    max_new_tokens=4) for i in range(2)]
    _, roomy = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                           block_size=4, max_batch=2)
    # 8-token prompt + 4 generated -> 3 blocks of 4; 5 usable blocks fit one
    # resident sequence but never two
    engine, tight = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                                block_size=4, max_batch=2, num_blocks=6)
    assert tight == roomy
    assert engine.stats()["pool"]["peak_blocks_in_use"] <= 5
    # batching never happened: the two requests were serialized
    assert engine.occupancy <= 0.5


def test_engine_paged_rejects_impossible_prompt():
    _, model, params = _make()
    engine = ContinuousBatchingEngine(model, params, max_batch=1, max_seq=32,
                                      kv_layout="paged", block_size=4,
                                      num_blocks=3)
    with pytest.raises(ValueError, match="never be resident"):
        engine.submit(Request(uid=0, prompt=np.arange(12, dtype=np.int32),
                              max_new_tokens=2))


# ---------------------------------------------------------------------------
# satellites: sampling + prompt bucketing
# ---------------------------------------------------------------------------


def test_engine_sampling_deterministic_and_batch_independent():
    """Seeded sampling: identical streams across runs, and a request's
    stream does not depend on what it was batched with."""
    _, model, params = _make()
    reqs = [Request(uid=i, prompt=np.arange(4 + i, dtype=np.int32),
                    max_new_tokens=4) for i in range(3)]
    kw = dict(temperature=1.0, top_k=8, sample_seed=7)
    _, a = _run_stream(model, params, layout="contig", impl_reqs=reqs, **kw)
    _, b = _run_stream(model, params, layout="contig", impl_reqs=reqs, **kw)
    assert a == b
    # uid 0 alone in a 1-slot engine: same stream as when batched
    _, solo = _run_stream(model, params, layout="contig", impl_reqs=reqs[:1],
                          max_batch=1, **kw)
    assert solo[0] == a[0]
    # different seed moves the stream (overwhelmingly likely)
    _, c = _run_stream(model, params, layout="contig", impl_reqs=reqs,
                       temperature=1.0, top_k=8, sample_seed=8)
    assert c != a


def test_engine_sampling_respects_top_k():
    """top_k=1 must reduce to greedy regardless of temperature."""
    _, model, params = _make()
    reqs = _ragged_reqs(9)
    _, greedy = _run_stream(model, params, layout="contig", impl_reqs=reqs)
    _, topk1 = _run_stream(model, params, layout="contig", impl_reqs=reqs,
                           temperature=5.0, top_k=1, sample_seed=3)
    assert topk1 == greedy


def test_engine_bucketing_bounds_prefill_compiles_token_identical():
    rng = np.random.default_rng(11)
    _, model, params = _make()
    reqs = [Request(uid=i, prompt=rng.integers(0, 64, 3 + i).astype(np.int32),
                    max_new_tokens=3) for i in range(6)]
    plain_engine, plain = _run_stream(model, params, layout="contig",
                                      impl_reqs=reqs)
    bucket_engine, bucketed = _run_stream(model, params, layout="contig",
                                          impl_reqs=reqs, bucket_prompts=True)
    assert bucketed == plain  # padding is invisible to causal prefill
    assert plain_engine.stats()["prefill_compiles"] == 6
    # 6 distinct lengths (3..8) collapse onto power-of-two buckets {4, 8}
    assert bucket_engine.stats()["prefill_compiles"] == 2
    assert set(bucket_engine.stats()["prefill_buckets"]) == {"4", "8"}


# ---------------------------------------------------------------------------
# refcounts, COW, cached-free tier, prefix index
# ---------------------------------------------------------------------------


def test_block_pool_cow_on_shared_block():
    pool = BlockPool(num_blocks=8, block_size=4)
    a = pool.allocate("a", 8)
    b = pool.allocate("b", 8, shared=a)  # full prefix hit: same physicals
    assert pool.block_table("b") == a
    assert pool.refcount(a[0]) == 2 and pool.shared_blocks == 2
    ev = pool.append_token("b", 7)  # write inside shared block 1 -> COW
    assert ev is not None and ev.kind == "cow" and ev.src == a[1]
    assert pool.block_table("b")[1] == ev.block != a[1]
    assert pool.block_table("a") == a  # the donor's table is untouched
    assert pool.refcount(a[1]) == 1 and pool.refcount(ev.block) == 1
    assert pool.append_token("b", 7) is None  # private now: no second copy
    pool.free("a")
    pool.free("b")
    pool.check_invariants()
    assert pool.blocks_in_use == 0 and pool.stats()["total_cow"] == 1


def test_block_pool_cached_free_resurrection_and_eviction():
    pool = BlockPool(num_blocks=6, block_size=4)
    evicted = []
    indexed = set()
    pool.cache_filter = lambda blk: blk in indexed
    pool.on_evict = lambda blk: (indexed.discard(blk), evicted.append(blk))
    a = pool.allocate("a", 12)
    indexed.update(a)
    pool.free("a")
    # indexed blocks park on the cached tier: evictable, hence still "free"
    assert pool.num_cached == 3 and pool.num_free == 5
    b = pool.allocate("b", 12, shared=a[:2])  # prefix hit resurrects two
    assert pool.block_table("b")[:2] == a[:2] and pool.num_cached == 1
    pool.allocate("c", 8)  # overflows the free list: evicts the cached LRU
    # the eviction callback purged the index, so the prefix layer can never
    # offer the recycled block as a hit again
    assert evicted == [a[2]] and a[2] not in indexed
    pool.check_invariants()


def test_prefix_index_full_partial_and_chained_semantics():
    from repro.paging import PrefixIndex
    idx = PrefixIndex(block_size=4)
    toks = np.arange(10, dtype=np.int32)  # 2 full blocks + 2-token tail
    idx.insert(toks, [5, 6, 7])
    assert idx.match(toks) == ([5, 6, 7], 10)  # full-prompt hit incl. tail
    # same full prefix, different/longer tail: full blocks only — the
    # partial block must never be mapped into a prompt that extends it
    longer = np.concatenate([toks[:8], np.array([1, 2, 3], np.int32)])
    assert idx.match(longer) == ([5, 6], 8)
    # diverging first block: nothing matches
    assert idx.match(np.arange(1, 11, dtype=np.int32)) == ([], 0)
    # identical block *content* at a different position must not hit
    # (keys are chained digests, not per-block content hashes)
    shifted = np.concatenate([np.full(4, 9, np.int32), toks[:4]])
    assert idx.match(shifted) == ([], 0)
    idx.forget_block(6)  # pool recycled it: the chain stops before it
    assert idx.match(toks) == ([5], 4)
    assert idx.stats()["hit_rate"] > 0


# ---------------------------------------------------------------------------
# engine: prefix sharing, COW, swap tier, dirty-row shipping
# ---------------------------------------------------------------------------


def test_engine_prefix_sharing_token_identical_and_skips_chunks():
    """Shared-prefix traffic: sharing on == sharing off, token for token,
    while skipping the hit chunks' prefill compute entirely."""
    _, model, params = _make()
    rng = np.random.default_rng(2)
    prefix = rng.integers(0, 64, 12).astype(np.int32)  # 3 full blocks of 4
    reqs = [Request(uid=i, prompt=np.concatenate(
                [prefix, rng.integers(0, 64, 2 + i).astype(np.int32)]),
                    max_new_tokens=3) for i in range(4)]
    eng_off, off = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                               block_size=4, prefix_cache=False)
    eng_on, on = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                             block_size=4)
    assert on == off
    st = eng_on.stats()
    assert st["prefill_chunks_skipped"] > 0
    assert st["prefill_chunks"] < eng_off.stats()["prefill_chunks"]
    assert eng_off.stats()["prefill_chunks_skipped"] == 0
    assert st["pool"]["prefix"]["hit_rate"] > 0
    assert st["pool"]["total_shares"] > 0


def test_engine_prefix_cow_on_identical_prompts():
    """Concurrent identical prompts share every block including the partial
    tail; each follower's first decode append pays exactly one COW copy and
    all streams stay identical to the unshared run."""
    _, model, params = _make()
    prompt = np.arange(3, 13, dtype=np.int32)  # 10 tokens: partial tail
    reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=4)
            for i in range(3)]
    _, off = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                         block_size=4, max_batch=3, prefix_cache=False)
    eng_on, on = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                             block_size=4, max_batch=3)
    assert on == off
    assert all(on[0] == on[u] for u in on)
    st = eng_on.stats()
    # 3 sequences share the tail block; the last owner standing appends in
    # place, so exactly two divergences pay a copy
    assert st["cow_copies"] == 2
    assert st["pool"]["total_cow"] == st["cow_copies"]
    assert st["pool"]["blocks_in_use"] == 0


def test_engine_swap_token_identical_over_committed_pool():
    """A pool too small for two residents' worst case: the swap policy
    parks cold residents on the host instead of serializing, and the
    resumed streams are token-identical to the roomy-pool run."""
    _, model, params = _make()
    reqs = [Request(uid=i, prompt=np.arange(2, 10, dtype=np.int32),
                    max_new_tokens=4) for i in range(3)]
    _, roomy = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                           block_size=4, max_batch=2)
    engine, tight = _run_stream(model, params, layout="paged", impl_reqs=reqs,
                                block_size=4, max_batch=2, num_blocks=6,
                                admission_policy="swap", prefix_cache=False)
    assert tight == roomy
    st = engine.stats()
    assert st["swap_outs"] >= 1 and st["swap_outs"] == st["swap_ins"]
    assert st["swapped"] == 0  # everyone came back and finished
    assert st["pool"]["blocks_in_use"] == 0
    assert len(engine.finished) == len(reqs)


def test_engine_swap_policy_requires_paged_layout():
    _, model, params = _make()
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingEngine(model, params, max_batch=2, max_seq=32,
                                 kv_layout="contig",
                                 admission_policy="swap")


def test_engine_dirty_rows_ship_only_changes():
    """Block-table rows reach the device only when they change; the
    device-resident table stays consistent with the host table."""
    _, model, params = _make()
    engine, _ = _run_stream(model, params, layout="paged",
                            impl_reqs=_ragged_reqs(), block_size=4)
    st = engine.stats()
    assert st["table_rows_shipped"] > 0
    # re-uploading every row every step would have moved far more rows
    assert st["table_rows_shipped"] < engine.decode_steps * engine.max_batch
    pending = set(engine.kv.take_dirty())  # releases after the last decode
    dev = np.asarray(engine._dev_tables)
    for row in range(engine.max_batch):
        if row not in pending:
            np.testing.assert_array_equal(dev[row], engine.kv.tables[row])


# ---------------------------------------------------------------------------
# random interleaving machine (shared with tests/test_property.py)
# ---------------------------------------------------------------------------


def _drive_pool_machine(seed: int, steps: int = 150, num_blocks: int = 10,
                        block_size: int = 4) -> None:
    """Random admit/share/append(+COW)/publish/free/swap interleaving
    against a shadow value model. After every op: pool conservation holds
    (no leaked or double-freed block, the null block never freed or
    mapped), every live sequence still reads the values it wrote (COW
    isolation), and host swap round-trips are value-identical."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(num_blocks, block_size)
    indexed = set()
    published = {}  # pid -> tuple of blocks (a fake prefix-cache entry)

    def on_evict(blk):
        indexed.discard(blk)
        for pid in [p for p, blks in published.items() if blk in blks]:
            del published[pid]

    pool.cache_filter = lambda blk: blk in indexed
    pool.on_evict = on_evict

    content = {}  # physical block -> last stamped value ("device data")
    live = {}     # seq -> {"tokens": int, "vals": [value per logical block]}
    parked = {}   # seq -> (tokens, host values) — swapped to "host memory"
    counters = {"seq": 0, "stamp": 0, "pid": 0}

    def stamp():
        counters["stamp"] += 1
        return counters["stamp"]

    def pick(d):
        return list(d)[int(rng.integers(0, len(d)))]

    for _ in range(steps):
        op = int(rng.integers(0, 12))
        if op <= 2:  # admit a fresh sequence
            n = int(rng.integers(1, 3 * block_size + 1))
            sid = counters["seq"]
            counters["seq"] += 1
            try:
                blocks = pool.allocate(sid, n)
            except BlockPoolExhausted:
                assert pool.owned_blocks(sid) == 0  # failed atomically
            else:
                for blk in blocks:
                    content[blk] = stamp()
                live[sid] = {"tokens": n,
                             "vals": [content[b] for b in blocks]}
        elif op <= 4 and published:  # admit sharing a published prefix
            shared = list(published[pick(published)])
            k = int(rng.integers(1, len(shared) + 1))
            shared = shared[:k]
            n = (k - 1) * block_size + int(rng.integers(1, block_size + 1))
            sid = counters["seq"]
            counters["seq"] += 1
            try:
                blocks = pool.allocate(sid, n, shared=shared)
            except BlockPoolExhausted:
                assert pool.owned_blocks(sid) == 0
            else:
                assert blocks[:k] == shared
                live[sid] = {"tokens": n,
                             "vals": [content[b] for b in blocks]}
        elif op <= 7 and live:  # append one token: alloc / COW / in place
            sid = pick(live)
            st = live[sid]
            if st["tokens"] >= 4 * block_size:
                continue  # cap one sequence's appetite
            pos = st["tokens"]
            idx = pos // block_size
            try:
                ev = pool.append_token(sid, pos)
            except BlockPoolExhausted:
                continue  # boundary alloc failed; table untouched
            st["tokens"] = pos + 1
            if ev is None:
                blk = pool.block_table(sid)[idx]
                # in-place writes are only legal into private blocks
                assert pool.refcount(blk) == 1
                content[blk] = stamp()
                st["vals"][idx] = content[blk]
            elif ev.kind == "cow":
                content[ev.block] = stamp()  # device copy + the new write
                st["vals"][idx] = content[ev.block]
                assert pool.refcount(ev.block) == 1
            else:  # boundary alloc
                content[ev.block] = stamp()
                st["vals"].append(content[ev.block])
                assert len(st["vals"]) == idx + 1
        elif op == 8 and live:  # publish (prefix-index) a live table
            blocks = pool.block_table(pick(live))
            indexed.update(blocks)
            published[counters["pid"]] = tuple(blocks)
            counters["pid"] += 1
        elif op == 9 and live:  # retire
            sid = pick(live)
            table = pool.block_table(sid)
            pool.free(sid)
            del live[sid]
            for blk in table:
                if pool.refcount(blk) == 0 and blk in indexed:
                    assert pool.is_cached(blk)  # parked for reuse, not lost
        elif op == 10 and live:  # swap out: host copy, then free the blocks
            sid = pick(live)
            st = live.pop(sid)
            parked[sid] = (st["tokens"],
                           [content[b] for b in pool.block_table(sid)])
            pool.free(sid)
        elif parked:  # swap in: fresh blocks, restored values
            sid = pick(parked)
            tokens, host = parked[sid]
            try:
                blocks = pool.allocate(sid, tokens)
            except BlockPoolExhausted:
                assert pool.owned_blocks(sid) == 0
            else:
                del parked[sid]
                for blk, val in zip(blocks, host):
                    content[blk] = val
                live[sid] = {"tokens": tokens, "vals": list(host)}
                # the host round trip restored every value exactly
                assert [content[b]
                        for b in pool.block_table(sid)] == host
        pool.check_invariants()
        for sid, st in live.items():
            table = pool.block_table(sid)
            assert len(table) == len(st["vals"])
            for blk, want in zip(table, st["vals"]):
                assert content[blk] == want, \
                    f"seed {seed}: seq {sid} block {blk} corrupted"
            assert BlockPool.NULL_BLOCK not in table

    for sid in list(live):
        pool.free(sid)
    pool.check_invariants()
    assert pool.blocks_in_use == 0  # nothing leaked (cached is reclaimable)
    assert pool.refcount(BlockPool.NULL_BLOCK) == 1


def test_block_pool_random_interleaving_invariants():
    """Deterministic sweep of the machine (tests/test_property.py drives the
    same machine under hypothesis when it is installed)."""
    for seed in range(20):
        _drive_pool_machine(seed)
