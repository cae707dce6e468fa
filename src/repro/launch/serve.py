"""Serving driver: continuous batching over a slotted KV cache.

The engine serves a *stream* of requests rather than one lockstep batch:
each of ``max_batch`` cache slots carries its own ``cache_len``, finished
sequences (EOS or length budget) retire immediately, and queued requests are
admitted into freed slots mid-stream — throughput is measured under the
ragged traffic a real endpoint sees, which is where Swan's pick-the-config-
that-fits-the-hardware argument bites for decode (KV-bandwidth-bound).

Mechanics per decode step:
  - one jitted decode over all slots with a per-slot (B,) cache_len vector;
    the cache is donated (``build_decode_step``) so the per-token update is
    in place, never a full-cache copy;
  - admission runs single-request prefill and splices the (L, 1, ..., P, d)
    prefill cache into the slot with one donated dynamic_update_slice;
  - idle slots decode garbage that is masked out on the host — their
    frozen cache_len keeps the math well-defined and their KV tiles are
    skipped by the Pallas decode kernel's length-clamped index maps.

KV layouts (``--kv-layout``):
  - ``contig`` reserves a (max_batch, max_seq) slab per layer — every slot
    pays for a full-length cache whether or not it uses it;
  - ``paged`` stores KV in fixed-size blocks from a shared pool
    (``repro.paging``): admission allocates just the blocks the prompt
    needs and ingests the prompt with *chunked paged prefill* — block-sized
    chunks written straight into pool blocks, no contiguous (1, P, ...)
    prefill cache, one compile for every prompt length — decode allocates
    on block boundaries, and retirement returns blocks to the pool — peak
    KV memory tracks *live tokens*, not slots x max_seq. The decode step
    routes each sequence through its (B, T) block table (scalar-prefetched
    by the paged flash-decode kernel); only table rows that changed since
    the last step are re-shipped to the device.

Prefix sharing (paged; on by default, ``--no-prefix-cache`` disables):
admission hash-conses prompt-prefix blocks — a request whose prompt prefix
was already prefilled maps the *same physical blocks* via pool refcounts
and skips the prefill compute for every hit chunk (a full-prompt hit runs
one read-only chunk just to recompute the last token's logits). Divergence
is copy-on-write: the first decode append into a shared block allocates a
private copy and device-copies the donor block. Retired prompts' blocks
park on a cached-free LRU tier — still allocatable, but a later identical
prefix resurrects them for free.

Host swap tier (``--admission-policy swap``): under pool pressure, cold
resident sequences' blocks are copied to host memory and freed instead of
serializing or shedding admission (LRU by last swap-in/admit step, with a
grace period as second chance); swapped sequences restore — bitwise — into
fresh blocks when headroom returns, with priority over new admissions.
``hold_blocks()`` co-tenant pressure can likewise force residents out to
host rather than starving admission.

Sampling: greedy by default; ``--temperature/--top-k`` switch the emitted
stream to seeded sampling with a per-request PRNG key (a request's stream
is independent of how it was batched). Parity gates keep using greedy.

Speculative decoding (``--draft-depth k``, ``--draft-source``): a draft
source (``repro.spec.draft``) proposes k cheap tokens per request, one
multi-token verify pass (the flash-decode kernel grown to a q-block)
scores the whole window, and the engine emits the accepted prefix plus
one non-draft token per round. Greedy mode is token-identical to
non-speculative decoding; sampled mode is distribution-faithful rejection
sampling on the same fold_in(seed, uid, index) streams. Rollback is pure
cache_len bookkeeping — rejected positions keep stale KV, masked dead by
the ragged-length kernels and overwritten next round. Draft depth is a
serving rung (``engine.jobs.ServeRung.draft_depth``): the arbiter walks
speculation down before capping slots when thermals bite.

``--bucket-prompts`` rounds admission prefill lengths up to power-of-two
buckets so the prefill jit cache stops growing per unique prompt length.

``--attn-impl pallas`` routes decode attention through the fused
single-query flash-decode kernel (kernels/flash_attention.flash_decode /
flash_decode_paged); ``auto`` consults kernels/backend.auto_decode_impl
(cache length x backend).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --batch 4 --requests 12 --prompt-len 32 --gen 16 [--kv-layout paged]
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.kernels.backend import auto_decode_impl
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (build_decode_step, build_paged_decode_step,
                                build_paged_prefill_step, build_sampler,
                                build_paged_spec_decode_step,
                                build_spec_decode_step)
from repro.spec.verify import greedy_verify, rejection_verify
from repro.models.registry import build_model
from repro.paging import BlockPoolExhausted, PagedKVCache

# families whose decode state is a slotted (L, B, Smax, ...) KV cache the
# engine knows how to splice; SSM/hybrid state and encoder-decoder cross
# caches stay on the legacy lockstep path below
ENGINE_FAMILIES = ("dense", "moe")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (P,) int32 prompt tokens
    max_new_tokens: int
    # graceful degradation: a queued request that has not been admitted
    # within deadline_steps engine steps of submission is dropped with a
    # "timeout" rejection instead of waiting forever (None = patient)
    deadline_steps: Optional[int] = None
    submitted_at: int = -1  # engine decode_steps at submit(); set by submit
    submitted_s: float = -1.0  # time.perf_counter() at submit(); set by submit


@dataclasses.dataclass
class Finished:
    uid: int
    tokens: List[int]  # generated token ids (first comes from prefill logits)
    reason: str  # "eos" | "length"
    prompt_len: int


@dataclasses.dataclass
class Rejected:
    """A request the engine declined instead of serving: load shedding under
    pool pressure, a queued-deadline timeout, or a drain. ``retry_after`` is
    the engine's estimate (in decode steps) of when resubmission could
    succeed — the serving analogue of an HTTP 503 Retry-After."""
    uid: int
    reason: str  # "shed" | "timeout" | "draining"
    retry_after: int


@dataclasses.dataclass
class SwappedSeq:
    """A mid-stream sequence whose KV blocks were evicted to host memory.

    Everything needed to resume exactly where it left off: the host copy of
    its blocks (logical order), the slot bookkeeping, and its worst-case
    block reservation. Restore is bitwise — the device -> host -> device
    round trip does not touch the values — so a swapped sequence's stream
    is token-identical to one that was never swapped."""
    uid: int
    generated: List[int]
    cache_len: int
    budget: int
    next_token: int
    host_kv: object  # numpy tree, leaves (L, n_blocks, block_size, ...)
    n_blocks: int
    worst: int  # worst-case block reservation to restore
    swapped_at: int  # engine decode_steps at swap-out (FIFO restore order)


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a model's KV-cache decode path."""

    def __init__(self, model, params, *, max_batch: int, max_seq: int,
                 eos_id: Optional[int] = None, cache_dtype=jnp.float32,
                 kv_layout: str = "contig", block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0, bucket_prompts: bool = False,
                 admission_policy: str = "serialize",
                 max_queue: Optional[int] = None,
                 prefix_cache: bool = True, swap_grace: int = 2,
                 draft_depth: int = 0, draft_source=None):
        cfg = model.cfg
        if cfg.family not in ENGINE_FAMILIES:
            raise ValueError(
                f"continuous batching needs a slotted KV cache; family "
                f"{cfg.family!r} is served by the legacy lockstep path")
        if kv_layout not in ("contig", "paged"):
            raise ValueError(f"kv_layout must be contig|paged, got {kv_layout!r}")
        if admission_policy not in ("serialize", "shed", "swap"):
            raise ValueError(f"admission_policy must be serialize|shed|swap, "
                             f"got {admission_policy!r}")
        if admission_policy == "swap" and kv_layout != "paged":
            raise ValueError("admission_policy='swap' needs the paged layout "
                             "(there are no blocks to evict under contig)")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.kv_layout = kv_layout
        self.block_size = block_size
        self.bucket_prompts = bucket_prompts
        # serving-rung knobs (engine.jobs.ServeJob migrates these live);
        # the as-built settings are what a None override restores
        self.slot_cap: Optional[int] = None
        self._base_model = model
        self._base_cache_dtype = jnp.dtype(cache_dtype)

        self.cache_len = np.zeros(max_batch, np.int32)
        self.tokens = np.zeros((max_batch, 1), np.int32)
        self.slot_uid: List[Optional[int]] = [None] * max_batch
        self.slot_budget = np.zeros(max_batch, np.int32)
        self.generated: List[List[int]] = [[] for _ in range(max_batch)]

        self.queue: Deque[Request] = collections.deque()
        self.finished: Dict[int, Finished] = {}
        # graceful degradation under overload (see _admit_waiting):
        #   "serialize" — head-of-line request waits for resources (the old
        #     implicit behavior: unbounded queueing, no request is refused);
        #   "shed" — a request that cannot get resources *now* is rejected
        #     with a retry-after hint, so admitted requests keep their
        #     latency instead of everyone missing deadlines together.
        self.admission_policy = admission_policy
        # under "shed" the waiting queue is bounded: a submission past the
        # bound is rejected up front with retry-after rather than parked on
        # an unbounded queue it may never leave. "serialize" queues without
        # limit (the implicit legacy behavior).
        if max_queue is None and admission_policy == "shed":
            max_queue = 2 * max_batch
        self.max_queue = max_queue
        self.accepting = True  # drain() flips this; submit() then rejects
        self.rejected: Dict[int, Rejected] = {}
        self.shed_count = 0
        self.timeout_count = 0
        self._held_blocks = 0  # pool blocks held by an external co-tenant
        self._hold_seq = 0
        self.decode_steps = 0
        self.tokens_out = 0
        self.admitted = 0        # requests admitted to a slot
        self.queue_wait_s = 0.0  # host seconds from submit to admission, summed
        self._active_slot_steps = 0
        self._uid_prompt_len: Dict[int, int] = {}
        self.prefill_lengths: Dict[int, int] = {}  # padded length -> count
        # prefix sharing / chunked prefill / swap / dirty-row accounting
        self.prefill_chunks = 0          # chunk-prefill kernel invocations
        self.prefill_chunks_skipped = 0  # prompt chunks skipped via prefix hit
        self.cow_copies = 0              # copy-on-write device block copies
        self.table_rows_shipped = 0      # dirty block-table rows sent to device
        self.table_uploads = 0           # full-table uploads (bulk dirt)
        self.swapped: Dict[int, SwappedSeq] = {}  # uid -> parked sequence
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_grace = max(0, int(swap_grace))
        # per-slot step of last admit/swap-in: LRU victim choice + grace
        self._resident_since = np.zeros(max_batch, np.int64)
        self.admission_waits: Dict[int, int] = {}  # uid -> steps queued
        self._stalled_steps = 0

        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._sampler = None
        if self.temperature > 0.0:
            self._sampler = build_sampler(self.temperature, self.top_k)
            base = jax.random.PRNGKey(sample_seed)
            # one jitted dispatch per step for the whole batch of keys, not a
            # host-side fold_in pair per slot
            self._keys = jax.jit(jax.vmap(
                lambda u, i: jax.random.fold_in(jax.random.fold_in(base, u), i)))
            # (B, S) key grid for speculative verify: same fold_in(seed,
            # uid, index) streams, one key per candidate emission index, so
            # a request's randomness stays batch-composition independent
            self._keys2 = jax.jit(jax.vmap(jax.vmap(
                lambda u, i: jax.random.fold_in(jax.random.fold_in(base, u), i))))
            self._rej_verify = jax.jit(functools.partial(
                rejection_verify, temperature=self.temperature,
                top_k=self.top_k))
        self._greedy_verify = jax.jit(greedy_verify)

        # speculative decoding: a draft source proposes k tokens per slot,
        # one multi-token verify pass scores the whole window, the engine
        # emits the accepted prefix + 1. Depth is a serving rung
        # (engine.jobs.ServeRung.draft_depth) the arbiter can walk down.
        self._base_draft_depth = max(0, int(draft_depth))
        self.draft_depth = self._base_draft_depth
        self.draft = draft_source
        if self.draft is None and self.draft_depth > 0:
            from repro.spec.draft import NGramDraft
            self.draft = NGramDraft()
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0

        self._prefill = jax.jit(model.prefill)  # one compile per prompt length

        if kv_layout == "paged":
            # virtual capacity per sequence: T blocks; defaults provision the
            # contiguous equivalent so paged-vs-contig is a layout change, not
            # a capacity change — benchmarks report *peak blocks in use*
            blocks_per_seq = -(-max_seq // block_size)
            if num_blocks is None:
                num_blocks = max_batch * blocks_per_seq + 1  # +1 null block
            self.kv = PagedKVCache(num_blocks, block_size, max_batch,
                                   blocks_per_seq, prefix_cache=prefix_cache)
            # admission control: worst-case blocks per resident request, so
            # allocate-on-boundary can never exhaust the pool mid-decode
            # (reservation is accounting only — peak_blocks_in_use still
            # reports blocks actually allocated)
            self._reserved: Dict[int, int] = {}
            self.cache = model.init_paged_cache(num_blocks, block_size,
                                                cache_dtype)
            # jitted, cache donated; sampling mode reads logits, not argmax
            self._decode = build_paged_decode_step(
                model, greedy=self._sampler is None)
            # chunked paged prefill: one compile (traced chunk start / last
            # pos) ingests any prompt, chunk grid == block grid so prefix
            # hits skip whole chunks; the read-only variant recomputes the
            # final chunk of a full-prompt hit without touching the pools
            self._prefill_chunk = build_paged_prefill_step(model)
            self._prefill_chunk_ro = build_paged_prefill_step(model,
                                                              write=False)
            # device-resident dense block table, updated row-wise from the
            # host table's dirty set instead of re-uploaded every step
            self._dev_tables = jnp.asarray(self.kv.tables)
            self.kv.take_dirty()  # the upload above covered the initial rows

            def set_row(tables, row, values):
                return jax.lax.dynamic_update_slice_in_dim(
                    tables, values[None], row, 0)

            self._set_row = jax.jit(set_row, donate_argnums=(0,))

            def copy_block(cache, src, dst):
                # COW: duplicate one physical block across every layer's pool
                def one(pool):  # (L, NB, bs, ...)
                    return pool.at[:, dst].set(jnp.take(pool, src, axis=1))

                return jax.tree_util.tree_map(one, cache)

            self._copy_block = jax.jit(copy_block, donate_argnums=(0,))

            def gather_blocks(cache, phys):
                return jax.tree_util.tree_map(lambda pool: pool[:, phys],
                                              cache)

            self._gather_blocks = jax.jit(gather_blocks)

            def put_blocks(cache, blocks, phys):
                return jax.tree_util.tree_map(
                    lambda pool, b: pool.at[:, phys].set(b.astype(pool.dtype)),
                    cache, blocks)

            self._put_blocks = jax.jit(put_blocks, donate_argnums=(0,))
        else:
            self.kv = None
            self.cache = model.init_cache(max_batch, max_seq, cache_dtype)
            # jitted, cache donated; sampling mode reads logits, not argmax
            self._decode = build_decode_step(model,
                                             greedy=self._sampler is None)

            def splice(cache, pcache, slot):
                def one(buf, pc):
                    start = (jnp.int32(0), slot) + (jnp.int32(0),) * (buf.ndim - 2)
                    return jax.lax.dynamic_update_slice(buf, pc.astype(buf.dtype), start)

                return jax.tree_util.tree_map(one, cache, pcache)

            self._splice = jax.jit(splice, donate_argnums=(0,))

        self._build_spec_steps(model)

    def _build_spec_steps(self, model) -> None:
        """(Re)build the multi-token verify step for the active layout;
        None when the family has no speculative decode path (the engine
        then falls back to one-token steps whatever the draft depth)."""
        if self.kv is not None:
            self._spec_decode = build_paged_spec_decode_step(model) \
                if model.paged_spec_decode_step is not None else None
        else:
            self._spec_decode = build_spec_decode_step(model) \
                if model.spec_decode_step is not None else None

    # -- request lifecycle -------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Queue a request; returns False (with a ``Rejected`` record) when
        the engine is draining. Malformed requests still raise."""
        if len(req.prompt) >= self.max_seq:
            raise ValueError(f"prompt {req.uid} ({len(req.prompt)} tokens) "
                             f"does not fit max_seq={self.max_seq}")
        if self.kv is not None and \
                self._worst_blocks(req) > self.kv.pool.num_usable:
            raise ValueError(
                f"request {req.uid} ({len(req.prompt)} prompt + "
                f"{req.max_new_tokens} budget) can never be resident: pool "
                f"has {self.kv.pool.num_usable} blocks of {self.block_size}")
        if not self.accepting:
            self._reject(req, "draining")
            return False
        if self.admission_policy == "shed" and self.max_queue is not None \
                and len(self.queue) >= self.max_queue:
            self._reject(req, "shed")
            return False
        req.submitted_at = self.decode_steps
        req.submitted_s = time.perf_counter()
        self.queue.append(req)
        return True

    def drain(self) -> None:
        """Stop admitting: refuse new submissions, shed the waiting queue,
        let residents stream to completion. Idempotent."""
        if not self.accepting:
            return
        self.accepting = False
        while self.queue:
            self._reject(self.queue.popleft(), "draining")

    def _retry_after(self) -> int:
        """Steps until an admission could plausibly succeed: the shortest
        remaining generation budget among residents (a slot and its blocks
        free when one retires), or 1 when the engine is idle."""
        remaining = [int(self.slot_budget[s]) - len(self.generated[s])
                     for s in range(self.max_batch)
                     if self.slot_uid[s] is not None]
        return max(1, min(remaining)) if remaining else 1

    def _reject(self, req: Request, reason: str) -> None:
        self.rejected[req.uid] = Rejected(
            uid=req.uid, reason=reason, retry_after=self._retry_after())
        if reason == "shed":
            self.shed_count += 1
        elif reason == "timeout":
            self.timeout_count += 1

    def _expire_deadlines(self) -> None:
        """Drop queued requests whose admission deadline has passed. Only
        *waiting* requests time out — a request already resident owns its
        resources and streams to completion."""
        if not any(r.deadline_steps is not None for r in self.queue):
            return
        keep: List[Request] = []
        for req in self.queue:
            waited = self.decode_steps - req.submitted_at
            if req.deadline_steps is not None and \
                    waited > req.deadline_steps:
                self._reject(req, "timeout")
            else:
                keep.append(req)
        self.queue = collections.deque(keep)

    # -- external memory pressure (chaos / co-tenant apps) ------------------

    def hold_blocks(self, n: int) -> int:
        """Let a co-tenant (the chaos injector) take up to ``n`` KV blocks
        out of the pool. Holds only what residents have not reserved, so a
        live sequence can never be starved mid-decode — exactly the pressure
        a neighboring app's allocation puts on admission. Under the swap
        policy, cold residents are evicted to host memory first so the
        co-tenant gets its blocks without starving admission afterwards.
        Returns the count actually held. No-op (0) under the contig layout."""
        if self.kv is None:
            return 0
        self.release_held()
        if self.admission_policy == "swap":
            # make room for the co-tenant by parking cold residents on host
            while self.kv.pool.num_usable - sum(self._reserved.values()) \
                    < int(n):
                victim = self._swap_victim()
                if victim is None:
                    break
                self._swap_out(victim)
        avail = self.kv.pool.num_usable - sum(self._reserved.values())
        take = max(0, min(int(n), avail, self.kv.pool.num_free))
        if take:
            self._hold_seq += 1
            self.kv.pool.allocate(("__hold__", self._hold_seq),
                                  take * self.block_size)
            self._held_blocks = take
        return take

    def release_held(self) -> None:
        """Return externally-held blocks to the pool (pressure clears)."""
        if self._held_blocks:
            self.kv.pool.free(("__hold__", self._hold_seq))
            self._held_blocks = 0

    # -- host-memory swap tier (admission_policy="swap") ---------------------

    def _swap_victim(self) -> Optional[int]:
        """LRU second-chance victim: the resident slot least recently
        admitted/swapped-in, skipping slots inside the grace window so a
        just-restored sequence is not immediately thrashed back out."""
        cands = [s for s in range(self.max_batch)
                 if self.slot_uid[s] is not None
                 and self.decode_steps - self._resident_since[s]
                 >= self.swap_grace]
        if not cands:
            return None
        return min(cands, key=lambda s: self._resident_since[s])

    def _swap_out(self, slot: int) -> None:
        """Evict a resident sequence's blocks to host memory and free them.

        The host copy is taken in logical-block order, so swap-in can
        restore into *any* fresh physical blocks — the round trip is
        bitwise and the resumed stream is token-identical."""
        uid = self.slot_uid[slot]
        blocks = self.kv.slot_blocks(slot)
        with obs.get_telemetry().span("serve.swap_out", uid=uid,
                                      blocks=len(blocks)):
            host = jax.tree_util.tree_map(
                np.asarray,
                self._gather_blocks(self.cache,
                                    jnp.asarray(blocks, jnp.int32)))
            self.swapped[uid] = SwappedSeq(
                uid=uid, generated=list(self.generated[slot]),
                cache_len=int(self.cache_len[slot]),
                budget=int(self.slot_budget[slot]),
                next_token=int(self.tokens[slot, 0]), host_kv=host,
                n_blocks=len(blocks), worst=self._reserved.pop(slot),
                swapped_at=self.decode_steps)
            self.slot_uid[slot] = None
            self.kv.release(slot)
            if self.draft is not None:
                self.draft.release(slot)
            self.swap_outs += 1

    def _swap_in(self, slot: int, sw: SwappedSeq) -> None:
        """Restore a parked sequence into fresh pool blocks and resume."""
        with obs.get_telemetry().span("serve.swap_in", uid=sw.uid,
                                      blocks=sw.n_blocks):
            blocks = self.kv.admit(slot, sw.uid,
                                   sw.n_blocks * self.block_size)
            self.cache = self._put_blocks(
                self.cache, jax.tree_util.tree_map(jnp.asarray, sw.host_kv),
                jnp.asarray(blocks, jnp.int32))
        self._reserved[slot] = sw.worst
        self.slot_uid[slot] = sw.uid
        self.slot_budget[slot] = sw.budget
        self.cache_len[slot] = sw.cache_len
        self.tokens[slot, 0] = sw.next_token
        self.generated[slot] = list(sw.generated)
        self._resident_since[slot] = self.decode_steps
        if self.draft is not None:
            # the parked record keeps no prompt, so the draft restarts from
            # the generated history alone — weaker proposals for a while,
            # never wrong ones (verification is sound whatever p is)
            self.draft.admit(slot, sw.generated)
        self.swap_ins += 1

    def _try_swap_in(self) -> None:
        """Restore parked sequences (FIFO) into free slots while their
        worst-case reservation fits. Runs before admission each step —
        swapped sequences already paid their queueing once."""
        if not self.swapped:
            return
        for uid in sorted(self.swapped, key=lambda u: self.swapped[u].swapped_at):
            if self.slot_cap is not None and \
                    sum(1 for u in self.slot_uid if u is not None) >= \
                    self.slot_cap:
                return
            free = [s for s in range(self.max_batch)
                    if self.slot_uid[s] is None]
            if not free:
                return
            sw = self.swapped[uid]
            fits = (self._held_blocks + sum(self._reserved.values())
                    + sw.worst <= self.kv.pool.num_usable) and \
                self.kv.pool.can_allocate(sw.n_blocks * self.block_size)
            if not fits:
                return  # FIFO: later (smaller) sequences must not starve it
            del self.swapped[uid]
            self._swap_in(free[0], sw)

    def _make_room(self, worst: int) -> bool:
        """Swap out LRU residents until a ``worst``-block reservation fits;
        False when no eligible victim remains (grace-protected or empty)."""
        while self._held_blocks + sum(self._reserved.values()) + worst \
                > self.kv.pool.num_usable:
            victim = self._swap_victim()
            if victim is None:
                return False
            self._swap_out(victim)
        return True

    def _worst_blocks(self, req: Request) -> int:
        """Blocks the request could ever own: prompt plus generation budget,
        capped by the cache-capacity retirement rule."""
        worst = min(len(req.prompt) + req.max_new_tokens, self.max_seq)
        return self.kv.pool.blocks_for(worst)

    def _prefill_len(self, P: int) -> int:
        """Admission prefill length: the true prompt length, rounded up to a
        block multiple under the paged layout (so KV splices whole blocks)
        and to the next power of two under ``bucket_prompts`` (so the
        prefill jit cache is bounded by log2(max_seq) entries)."""
        L = P
        if self.bucket_prompts:
            L = 1 << (max(L, 1) - 1).bit_length()
        if self.kv is not None:
            bs = self.block_size
            L = -(-L // bs) * bs
            return min(L, self.kv.max_blocks_per_seq * bs)
        return min(L, self.max_seq)

    def _pick_token(self, logits_row, uid: int, index: int) -> int:
        """logits_row: (V,). Greedy unless a sampler is configured.

        Sampling keys are a pure function of (seed, uid, index), so a
        request's sampled stream is independent of slot placement and batch
        composition."""
        if self._sampler is None:
            return int(jnp.argmax(logits_row))
        key = self._keys(jnp.asarray([uid], jnp.int32),
                         jnp.asarray([index], jnp.int32))
        return int(self._sampler(logits_row[None], key)[0])

    def _admit(self, slot: int, req: Request) -> None:
        P = len(req.prompt)
        if self.kv is not None:
            logits = self._paged_prefill(slot, req)
        else:
            Lp = self._prefill_len(P)
            self.prefill_lengths[Lp] = self.prefill_lengths.get(Lp, 0) + 1
            batch = {"tokens": jnp.asarray(np.pad(req.prompt, (0, Lp - P)),
                                           jnp.int32)[None]}
            if Lp != P:
                # causal attention keeps every position < P unaffected by the
                # right-padding; logits must come from the true last token
                batch["last_pos"] = jnp.int32(P - 1)
            with obs.get_telemetry().span("serve.prefill", uid=req.uid,
                                          prompt_len=P, padded_len=Lp):
                logits, pcache = self._prefill(self.params, batch)
                self.cache = self._splice(self.cache, pcache,
                                          jnp.int32(slot))
        first = self._pick_token(logits[0, -1], req.uid, 0)
        self.slot_uid[slot] = req.uid
        self.slot_budget[slot] = req.max_new_tokens
        self.cache_len[slot] = P
        self.tokens[slot, 0] = first
        self.generated[slot] = [first]
        self._uid_prompt_len[req.uid] = P
        self._resident_since[slot] = self.decode_steps
        self.admission_waits[req.uid] = max(
            0, self.decode_steps - max(req.submitted_at, 0))
        self.admitted += 1
        if req.submitted_s >= 0:
            self.queue_wait_s += time.perf_counter() - req.submitted_s
        self.tokens_out += 1
        if self.draft is not None:
            self.draft.admit(slot, [int(t) for t in req.prompt])
            self.draft.commit(slot, [], first)  # first emission, no drafts
        if self._should_retire(slot, first):  # budget of 1, or prefill hit EOS
            self._retire(slot, "eos" if first == self.eos_id else "length")

    def _paged_prefill(self, slot: int, req: Request):
        """Chunked paged prefill with prefix sharing; returns last-token
        logits. Cache-hit prefix chunks skip the kernel entirely (their
        blocks are mapped, already populated); only miss-suffix chunks run,
        writing prompt KV straight into the slot's pool blocks. A full-prompt
        hit still runs the *final* chunk read-only — shared blocks must not
        be rewritten, but the last position's logits are needed to emit the
        first token."""
        P = len(req.prompt)
        bs = self.block_size
        self._reserved[slot] = self._worst_blocks(req)
        shared, covered = self.kv.match_prefix(req.prompt)
        blocks = self.kv.admit(slot, req.uid, P, shared=shared)
        n_blocks = len(blocks)
        Lp = n_blocks * bs
        self.prefill_lengths[Lp] = self.prefill_lengths.get(Lp, 0) + 1
        table_row = jnp.asarray(self.kv.tables[slot:slot + 1])
        padded = np.pad(np.asarray(req.prompt, np.int32), (0, Lp - P))
        first_miss = n_blocks if covered >= P else covered // bs
        logits = None
        tel = obs.get_telemetry()
        for c in range(first_miss, n_blocks):
            toks = jnp.asarray(padded[c * bs:(c + 1) * bs])[None]
            last = jnp.int32(min(P - 1 - c * bs, bs - 1))
            with tel.span("serve.prefill_chunk", uid=req.uid, chunk=c,
                          of=n_blocks):
                logits, self.cache = self._prefill_chunk(
                    self.params, self.cache, toks, jnp.int32(c * bs),
                    table_row, last)
            self.prefill_chunks += 1
        self.prefill_chunks_skipped += first_miss
        if logits is None:  # every block hit: read-only last-chunk recompute
            c = n_blocks - 1
            toks = jnp.asarray(padded[c * bs:(c + 1) * bs])[None]
            with tel.span("serve.prefill_chunk", uid=req.uid, chunk=c,
                          of=n_blocks, readonly=True):
                logits, _ = self._prefill_chunk_ro(
                    self.params, self.cache, toks, jnp.int32(c * bs),
                    table_row, jnp.int32(P - 1 - c * bs))
            self.prefill_chunks += 1
        self.kv.index_prompt(slot, req.prompt)
        return logits

    def _should_retire(self, slot: int, tok: int) -> bool:
        if self.eos_id is not None and tok == self.eos_id:
            return True
        if len(self.generated[slot]) >= int(self.slot_budget[slot]):
            return True
        # the next decode writes at position cache_len; retire only once that
        # would fall off the cache — position max_seq-1 is still serveable
        return self.cache_len[slot] >= self.max_seq

    def _retire(self, slot: int, reason: str) -> None:
        uid = self.slot_uid[slot]
        self.finished[uid] = Finished(
            uid=uid, tokens=list(self.generated[slot]), reason=reason,
            prompt_len=self._uid_prompt_len.pop(uid))
        self.slot_uid[slot] = None
        if self.draft is not None:
            self.draft.release(slot)
        if self.kv is not None:
            # blocks go back to the pool; the slot's table row resets to the
            # null block so its masked idle-slot writes stay harmless
            self.kv.release(slot)
            self._reserved.pop(slot, None)
        # cache_len stays frozen: the stale KV keeps idle-slot math
        # well-defined and is overwritten by the next admission's splice

    # -- serving-rung knobs (live-migratable; see engine.jobs.ServeJob) -----

    def set_slot_cap(self, cap: Optional[int]) -> None:
        """Cap concurrently-resident requests (decode microbatch cap).

        Takes effect at admission: resident sequences above a lowered cap
        keep streaming and the population shrinks as they retire — no
        request is ever evicted mid-decode. ``None`` removes the cap."""
        self.slot_cap = None if cap is None else max(1, int(cap))

    def set_kv_dtype(self, dtype=None) -> None:
        """Cast the live KV cache (``None`` restores the as-built dtype).

        Halving cache bytes (bf16) halves the bandwidth every decode step
        streams — the serving analogue of a bf16 training rung. Lossy on
        the way down: re-upcasting does not recover the rounded bits."""
        if isinstance(dtype, str):
            dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                     "float16": jnp.float16}[dtype]
        dtype = self._base_cache_dtype if dtype is None else jnp.dtype(dtype)
        leaves = jax.tree_util.tree_leaves(self.cache)
        if not leaves or all(a.dtype == dtype for a in leaves):
            return
        self.cache = jax.tree_util.tree_map(
            lambda a: a.astype(dtype), self.cache)

    def set_attn_impl(self, impl: Optional[str]) -> None:
        """Rebuild the decode/prefill callables under a different attention
        impl (``None`` restores the as-built model). Params, cache and all
        slot bookkeeping carry over — only the compiled steps change."""
        if impl == getattr(self, "_attn_impl_override", None):
            return
        self._attn_impl_override = impl
        if impl is None:
            model = self._base_model
        else:
            # rebuild from the as-built model's own kwargs so only the
            # attention impl changes (chunk/remat/dtype/moe_cf carry over)
            from repro.models.registry import rebuild_model
            model = rebuild_model(self._base_model, impl=impl)
        if model is self.model:
            return
        self.model = model
        self._prefill = jax.jit(model.prefill)
        if self.kv is not None:
            self._decode = build_paged_decode_step(
                model, greedy=self._sampler is None)
            self._prefill_chunk = build_paged_prefill_step(model)
            self._prefill_chunk_ro = build_paged_prefill_step(model,
                                                              write=False)
        else:
            self._decode = build_decode_step(model,
                                             greedy=self._sampler is None)
        self._build_spec_steps(model)

    def set_draft_depth(self, k: Optional[int]) -> None:
        """Serving-rung knob: verify ``k`` draft tokens per engine step
        (0 disables speculation; ``None`` restores the as-built depth).

        Takes effect on the next step — residents and the KV cache are
        untouched, because rollback is already cache_len bookkeeping: a
        depth change just alters how many candidate positions the next
        verify pass scores. Emitted streams are invariant to depth (greedy
        is token-identical at any k; sampled stays distribution-faithful),
        which is what makes draft depth safe to walk under thermal or
        energy pressure."""
        depth = self._base_draft_depth if k is None else max(0, int(k))
        if depth == self.draft_depth:
            return
        self.draft_depth = depth
        if depth > 0 and self.draft is None:
            # late enable on an engine built without a source: self-draft
            # from each resident's own emitted history
            from repro.spec.draft import NGramDraft
            self.draft = NGramDraft()
            for slot in range(self.max_batch):
                if self.slot_uid[slot] is not None:
                    self.draft.admit(slot, self.generated[slot])

    # -- stepping ----------------------------------------------------------

    def _pool_pressure(self, req: Request) -> bool:
        """True when admitting ``req`` could starve a resident later:
        its worst case plus every resident's reservation plus externally
        held blocks would overrun the pool."""
        if self.kv is None:
            return False
        return self._held_blocks + sum(self._reserved.values()) + \
            self._worst_blocks(req) > self.kv.pool.num_usable

    def _admit_waiting(self) -> None:
        for slot in range(self.max_batch):
            while True:
                if not self.queue or not self.accepting:
                    return
                if self.slot_cap is not None and \
                        sum(1 for u in self.slot_uid if u is not None) >= \
                        self.slot_cap:
                    return
                if self.slot_uid[slot] is not None:
                    break  # occupied; try the next slot
                head = self.queue[0]
                if self._pool_pressure(head):
                    # reserve the head request's worst case against every
                    # resident's, so allocate-on-boundary can never corrupt
                    # a live sequence mid-decode. Under pressure the policy
                    # decides who pays: "serialize" stalls the whole queue
                    # behind the head (retried next step); "shed" rejects
                    # the head with a retry-after hint and lets a smaller
                    # request behind it take the slot; "swap" parks cold
                    # residents' blocks on the host to make room, falling
                    # back to serialize when every resident is grace-
                    # protected.
                    if self.admission_policy == "swap" and \
                            self._make_room(self._worst_blocks(head)):
                        pass  # pressure cleared; fall through to admission
                    elif self.admission_policy == "shed":
                        # shed the head and move on to the next slot: at
                        # most max_batch rejections per step, so sustained
                        # pressure degrades the queue gradually instead of
                        # emptying it in one tick
                        self._reject(self.queue.popleft(), "shed")
                        break
                    else:
                        return
                req = self.queue.popleft()
                try:
                    self._admit(slot, req)
                except BlockPoolExhausted:
                    # the reservation check makes this unreachable for the
                    # engine's own traffic; a racing external allocation
                    # (between the check and the pool call) can still trip
                    # it. kv.admit fails atomically before any slot state is
                    # written, so rolling back the reservation restores the
                    # engine — then degrade per policy rather than crash.
                    self._reserved.pop(slot, None)
                    if self.admission_policy == "shed":
                        self._reject(req, "shed")
                    else:
                        self.queue.appendleft(req)
                        return
                break

    def _append_positions(self, active: List[int], n: int) -> None:
        """Allocate-on-boundary for the next ``n`` cache positions of every
        active slot (n = 1 plain decode, k+1 speculative window). Re-append
        of an already-owned private position is a no-op, so a speculative
        round that rolled back simply re-covers the same positions."""
        for slot in active:
            cl = int(self.cache_len[slot])
            for i in range(n):
                ev = self.kv.append(slot, cl + i)
                if ev is not None and ev.kind == "cow":
                    # first divergent write into a shared block: give this
                    # sequence a private copy, device-side, before decode
                    with obs.get_telemetry().span("serve.cow_copy",
                                                  slot=slot, src=ev.src,
                                                  dst=ev.block):
                        self.cache = self._copy_block(
                            self.cache, jnp.int32(ev.src),
                            jnp.int32(ev.block))
                    self.cow_copies += 1

    def _ship_dirty_tables(self) -> None:
        rows = self.kv.take_dirty()
        if not rows:
            return
        # ship only the table rows that changed since last step; bulk dirt
        # (e.g. after a swap storm) falls back to one full upload instead
        # of a row-by-row drip
        if len(rows) > max(1, self.max_batch // 2):
            self._dev_tables = jnp.asarray(self.kv.tables)
            self.table_uploads += 1
        else:
            for r in rows:
                self._dev_tables = self._set_row(
                    self._dev_tables, jnp.int32(r),
                    jnp.asarray(self.kv.tables[r]))
        self.table_rows_shipped += len(rows)

    def _spec_k(self, active: List[int]) -> int:
        """Effective draft depth this step: the configured depth clamped so
        the verify window (a) never writes past the cache (positions
        cache_len..cache_len+k must fit), and (b) never allocates past a
        paged reservation — k at most the smallest remaining generation
        budget keeps the worst-case block accounting exact. 0 falls back
        to the one-token step."""
        if self.draft_depth < 1 or self.draft is None or \
                self._spec_decode is None:
            return 0
        k = min(self.draft_depth,
                self.max_seq - 1 - max(int(self.cache_len[s])
                                       for s in active),
                min(int(self.slot_budget[s]) - len(self.generated[s])
                    for s in active))
        return max(k, 0)

    def step(self) -> List[Tuple[int, int]]:
        """Admit waiting requests, run one batched decode, retire finishers.

        Returns (uid, token) pairs emitted this step.
        """
        self._expire_deadlines()
        if self.swapped:
            self._try_swap_in()
        tel = obs.get_telemetry()
        with tel.span("serve.admit"):
            self._admit_waiting()
        active = [s for s in range(self.max_batch) if self.slot_uid[s] is not None]
        if not active:
            # nothing resident but work still pending (queued behind held
            # blocks, or parked on host with no headroom): guard against a
            # run() loop that can never make progress
            if self.queue or self.swapped:
                self._stalled_steps += 1
                if self._stalled_steps > 10000:
                    raise RuntimeError(
                        f"engine stalled: {len(self.queue)} queued, "
                        f"{len(self.swapped)} swapped, no admissible slot "
                        f"for {self._stalled_steps} steps")
            return []
        self._stalled_steps = 0
        k = self._spec_k(active)
        if k >= 1:
            return self._step_speculative(active, k)
        tables = ()
        if self.kv is not None:
            self._append_positions(active, 1)
            self._ship_dirty_tables()
            tables = (self._dev_tables,)
        # one decode step, from its dispatch to the host's read of the next
        # tokens; serve.decode_wait is the time the host blocks on the device
        with tel.span("serve.decode", batch=len(active)):
            next_tok, logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(self.tokens),
                jnp.asarray(self.cache_len), *tables)
            if self._sampler is None:
                with tel.span("serve.decode_wait"):
                    next_np = np.asarray(next_tok)
            else:
                uids = np.asarray([self.slot_uid[s]
                                   if self.slot_uid[s] is not None else 0
                                   for s in range(self.max_batch)], np.int32)
                idxs = np.asarray([len(self.generated[s])
                                   for s in range(self.max_batch)], np.int32)
                keys = self._keys(jnp.asarray(uids), jnp.asarray(idxs))
                with tel.span("serve.decode_wait"):
                    next_np = np.asarray(
                        self._sampler(logits[:, -1], keys))[:, None]
        self.decode_steps += 1
        self._active_slot_steps += len(active)
        emitted = []
        with tel.span("serve.emit", batch=len(active)):
            for slot in active:
                tok = int(next_np[slot, 0])
                self.generated[slot].append(tok)
                self.cache_len[slot] += 1
                self.tokens[slot, 0] = tok
                self.tokens_out += 1
                emitted.append((self.slot_uid[slot], tok))
                if self.draft is not None:
                    # keep the draft's view of the stream current even while
                    # speculation is off (depth walked to 0, or a clamped
                    # round)
                    self.draft.commit(slot, [], tok)
                if self._should_retire(slot, tok):
                    self._retire(slot, "eos" if (self.eos_id is not None and
                                                 tok == self.eos_id)
                                 else "length")
        return emitted

    def _step_speculative(self, active: List[int], k: int) -> List[Tuple[int, int]]:
        """One speculative round: draft k tokens per active slot, score the
        (k+1)-token window [last_emitted, d_1..d_k] in ONE verify pass,
        emit the accepted prefix plus exactly one non-draft token.

        Rollback is pure cache_len bookkeeping: the verify pass scattered
        KV for every window position, but cache_len only advances over the
        emitted tokens — rejected positions' KV stays resident, masked dead
        by the ragged-length kernels, and is overwritten in place by the
        next round's scatter. Greedy verification makes the emitted stream
        token-identical to one-token greedy decode; sampled mode is
        distribution-faithful rejection sampling on the engine's
        fold_in(seed, uid, index) streams."""
        S = k + 1
        tel = obs.get_telemetry()
        with tel.span("serve.spec_draft", batch=len(active), k=k):
            drafts, dprobs = self.draft.propose(active, k)
        win = np.zeros((self.max_batch, S), np.int32)
        win[:, 0] = self.tokens[:, 0]
        probs_b = None
        if dprobs is not None:
            probs_b = np.zeros((self.max_batch, k, dprobs.shape[-1]),
                               np.float32)
        for row, slot in enumerate(active):
            win[slot, 1:] = drafts[row]
            if probs_b is not None:
                probs_b[slot] = dprobs[row]
        tables = ()
        if self.kv is not None:
            self._append_positions(active, S)
            self._ship_dirty_tables()
            tables = (self._dev_tables,)
        with tel.span("serve.spec_verify", batch=len(active), k=k):
            logits, self.cache = self._spec_decode(
                self.params, self.cache, jnp.asarray(win),
                jnp.asarray(self.cache_len), *tables)
        if self._sampler is None:
            toks, n_emit = self._greedy_verify(logits,
                                               jnp.asarray(win[:, 1:]))
        else:
            uids = np.asarray(
                [self.slot_uid[s] if self.slot_uid[s] is not None else 0
                 for s in range(self.max_batch)], np.int32)
            idxs = (np.asarray([len(self.generated[s])
                                for s in range(self.max_batch)],
                               np.int32)[:, None]
                    + np.arange(S, dtype=np.int32)[None])
            keys = self._keys2(
                jnp.asarray(np.broadcast_to(uids[:, None],
                                            (self.max_batch, S))),
                jnp.asarray(idxs))
            toks, n_emit = self._rej_verify(
                logits, jnp.asarray(win[:, 1:]),
                None if probs_b is None else jnp.asarray(probs_b), keys)
        tok_np = np.asarray(toks)
        n_np = np.asarray(n_emit)
        self.decode_steps += 1
        self._active_slot_steps += len(active)
        emitted: List[Tuple[int, int]] = []
        accepted_total = 0
        for slot in active:
            uid = self.slot_uid[slot]
            seq = [int(t) for t in tok_np[slot, :int(n_np[slot])]]
            self.spec_rounds += 1
            self.spec_drafted += k
            self.spec_accepted += len(seq) - 1
            accepted_total += len(seq) - 1
            retired = False
            for tok in seq:
                self.generated[slot].append(tok)
                self.cache_len[slot] += 1
                self.tokens[slot, 0] = tok
                self.tokens_out += 1
                emitted.append((uid, tok))
                if self._should_retire(slot, tok):
                    # the retire trims the round: later accepted tokens are
                    # dropped and their KV stays masked dead, exactly like
                    # a rejection
                    self._retire(slot, "eos" if (self.eos_id is not None and
                                                 tok == self.eos_id)
                                 else "length")
                    retired = True
                    break
            if not retired:
                self.draft.commit(slot, seq[:-1], seq[-1])
        m = tel.metrics
        m.counter("spec_drafted_total",
                  "draft tokens proposed to the verifier").inc(k * len(active))
        m.counter("spec_accepted_total",
                  "draft tokens accepted by the verifier").inc(accepted_total)
        m.gauge("spec_acceptance_rate",
                "running accepted/drafted ratio").set(
            self.spec_accepted / max(1, self.spec_drafted))
        return emitted

    def run(self, requests: List[Request]) -> Dict[int, Finished]:
        for req in requests:
            self.submit(req)
        while self.queue or self.swapped or \
                any(u is not None for u in self.slot_uid):
            self.step()
        return self.finished

    @property
    def has_work(self) -> bool:
        """True while anything is queued, resident, or swapped out."""
        return bool(self.queue) or bool(self.swapped) or \
            any(u is not None for u in self.slot_uid)

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots active per decode step (batching efficiency)."""
        if not self.decode_steps:
            return 0.0
        return self._active_slot_steps / (self.decode_steps * self.max_batch)

    def kv_bytes(self, *, peak: bool = False) -> int:
        """KV-cache memory footprint in bytes.

        ``contig``: the whole (max_batch, max_seq) slab tree — allocated up
        front whatever the traffic. ``paged``: pool bytes scaled to blocks in
        use (``peak`` gives the high-water mark) — what a block-granular
        allocator would actually have had to back.
        """
        total = sum(a.size * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(self.cache))
        if self.kv is None:
            return total
        blocks = self.kv.pool.peak_blocks_in_use if peak \
            else self.kv.pool.blocks_in_use
        return int(total * blocks / self.kv.pool.num_blocks)

    def stats(self) -> Dict:
        """Engine-level stats: occupancy, prefill buckets, pool accounting."""
        out = {
            "decode_steps": self.decode_steps,
            "tokens_out": self.tokens_out,
            "admitted": self.admitted,
            "queue_wait_s": self.queue_wait_s,
            "occupancy": round(self.occupancy, 4),
            "kv_layout": self.kv_layout,
            "prefill_buckets": {str(k): v for k, v in
                                sorted(self.prefill_lengths.items())},
            "prefill_compiles": len(self.prefill_lengths),
            "kv_bytes": self.kv_bytes(),
            "admission_policy": self.admission_policy,
            "accepting": self.accepting,
            "shed": self.shed_count,
            "timeouts": self.timeout_count,
            "rejected": len(self.rejected),
        }
        waits = list(self.admission_waits.values())
        out["admission_wait_mean"] = \
            round(sum(waits) / len(waits), 3) if waits else 0.0
        out["admission_wait_max"] = max(waits) if waits else 0
        out["draft_depth"] = self.draft_depth
        if self.spec_rounds:
            out["spec_rounds"] = self.spec_rounds
            out["spec_drafted"] = self.spec_drafted
            out["spec_accepted"] = self.spec_accepted
            out["spec_acceptance"] = round(
                self.spec_accepted / max(1, self.spec_drafted), 4)
        if self.kv is not None:
            out["held_blocks"] = self._held_blocks
            out["prefill_chunks"] = self.prefill_chunks
            out["prefill_chunks_skipped"] = self.prefill_chunks_skipped
            out["cow_copies"] = self.cow_copies
            out["table_rows_shipped"] = self.table_rows_shipped
            out["table_uploads"] = self.table_uploads
            out["swapped"] = len(self.swapped)
            out["swap_outs"] = self.swap_outs
            out["swap_ins"] = self.swap_ins
            live = {self.slot_uid[s]: int(self.cache_len[s])
                    for s in range(self.max_batch)
                    if self.slot_uid[s] is not None}
            out["pool"] = self.kv.stats(live)
            out["peak_kv_bytes"] = self.kv_bytes(peak=True)
        return out


# ---------------------------------------------------------------------------
# legacy lockstep path (SSM / hybrid / enc-dec / VLM families)
# ---------------------------------------------------------------------------


def lockstep_generate(model, params, batch, *, prompt_len: int,
                      gen: int) -> jnp.ndarray:
    """Fixed-batch, fixed-length generation (the pre-engine serve loop)."""
    cfg = model.cfg
    max_len = prompt_len + gen
    bsz = batch["tokens"].shape[0]
    if cfg.family == "encdec":
        from repro.models import encdec as E
        cache = model.init_cache(bsz, max_len, jnp.float32)
        enc_h = E.encode(params, cfg, jnp.asarray(batch["audio_embed"]))
        ks, vs = [], []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a, i=i: a[i], params["dec_layers"])
            hd = cfg.head_dim
            B, Senc = enc_h.shape[:2]
            # head-major, the layout decode reads cross KV in
            ks.append(jnp.swapaxes((enc_h @ lp["cross_attn"]["wk"]).reshape(
                B, Senc, cfg.n_kv_heads, hd), 1, 2))
            vs.append(jnp.swapaxes((enc_h @ lp["cross_attn"]["wv"]).reshape(
                B, Senc, cfg.n_kv_heads, hd), 1, 2))
        cache["cross"] = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
        tokens = jnp.zeros((bsz, 1), jnp.int32)
        pos0 = 0
    else:
        logits, pcache = model.prefill(params, {k: jnp.asarray(v) for k, v in batch.items()})
        cache = model.init_cache(bsz, max_len, jnp.float32)

        def splice(buf, pc):  # KV caches keep the sequence on axis -2
            if buf.ndim >= 3 and pc.shape[-2] == prompt_len and buf.shape[1] == bsz:
                return buf.at[..., :prompt_len, :].set(pc.astype(buf.dtype))
            return pc.astype(buf.dtype) if pc.shape == buf.shape else buf

        if cfg.family in ("ssm", "hybrid"):
            cache = jax.tree_util.tree_map(lambda b, p: p.astype(b.dtype), cache, pcache)
        else:
            cache = jax.tree_util.tree_map(splice, cache, pcache)
        tokens = jnp.argmax(logits[:, -1:, :], -1).astype(jnp.int32)
        pos0 = prompt_len

    step = build_decode_step(model)
    out_tokens = [tokens]
    for t in range(gen - 1):
        tokens, _, cache = step(params, cache, tokens, jnp.int32(pos0 + t))
        out_tokens.append(tokens)
    return jnp.concatenate(out_tokens, axis=1)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _synthetic_requests(rng, n: int, prompt_len: int, gen: int,
                        vocab: int) -> List[Request]:
    """A ragged request stream: prompt lengths and budgets vary per request
    so retirement and admission interleave instead of running in lockstep."""
    reqs = []
    for uid in range(n):
        p = max(2, prompt_len + int(rng.integers(-prompt_len // 2, prompt_len // 2 + 1)))
        g = max(1, gen + int(rng.integers(-gen // 2, gen // 2 + 1)))
        reqs.append(Request(uid=uid,
                            prompt=rng.integers(0, vocab, p).astype(np.int32),
                            max_new_tokens=g))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="serving slots")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests in the stream (default: 3x batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="cache capacity (default: 2*(prompt+gen))")
    ap.add_argument("--attn-impl", default="auto",
                    choices=("auto", "naive", "pallas"),
                    help="decode attention path; auto resolves via "
                         "kernels/backend.auto_decode_impl")
    ap.add_argument("--kv-layout", default="contig",
                    choices=("contig", "paged"),
                    help="KV cache layout: contiguous per-slot slabs, or "
                         "block-pooled paged cache (repro.paging)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged layout: token positions per KV block")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged layout: physical blocks in the pool "
                         "(default: contiguous-equivalent capacity)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter for sampling (0 = full vocab)")
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--admission-policy", default="serialize",
                    choices=("serialize", "shed", "swap"),
                    help="overload behavior: serialize queues behind the "
                         "head-of-line request; shed rejects with retry-after; "
                         "swap parks cold residents' blocks in host memory "
                         "(paged layout only)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged layout: disable prompt-prefix block sharing")
    ap.add_argument("--swap-grace", type=int, default=2,
                    help="swap policy: steps a just-admitted/restored "
                         "sequence is protected from swap-out")
    ap.add_argument("--draft-depth", type=int, default=0,
                    help="speculative decoding: draft tokens verified per "
                         "engine step (0 = off); also a serving rung the "
                         "arbiter walks down under pressure")
    ap.add_argument("--draft-source", default="ngram",
                    help="where drafts come from: 'ngram' (self-drafting "
                         "n-gram head) or a registry arch name served "
                         "reduced as a draft model")
    ap.add_argument("--bucket-prompts", action="store_true",
                    help="round admission prefill lengths up to power-of-two "
                         "buckets (bounds prefill jit-cache growth)")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--lockstep", action="store_true",
                    help="legacy fixed-batch loop (forced for SSM/hybrid/"
                         "encdec/VLM families)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--telemetry-out", default=None,
                    help="directory for the repro.obs telemetry bundle "
                         "(metrics.jsonl, spans.jsonl, trace.json, "
                         "audit.json)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    tel = obs.enable() if args.telemetry_out else None
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "cnn":
        raise SystemExit("CNN archs have no decode path")

    max_seq = args.max_seq or 2 * (args.prompt_len + args.gen)
    impl = args.attn_impl
    if impl == "auto":
        impl = auto_decode_impl(max_seq)
    model = build_model(cfg, impl=impl)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(args.seed)

    if args.lockstep or cfg.family not in ENGINE_FAMILIES:
        from repro.data.pipeline import synthetic_lm_batch
        batch = synthetic_lm_batch(rng, args.batch, args.prompt_len, cfg.vocab_size)
        if cfg.family == "vlm":
            batch["image_embed"] = jnp.asarray(
                rng.standard_normal((args.batch, cfg.n_image_tokens, cfg.d_model)),
                jnp.float32) * 0.02
        if cfg.family == "encdec":
            batch["audio_embed"] = jnp.asarray(
                rng.standard_normal((args.batch, cfg.n_audio_frames, cfg.d_model)),
                jnp.float32) * 0.02
        t0 = time.time()
        gen = lockstep_generate(model, params, batch,
                                prompt_len=args.prompt_len, gen=args.gen)
        dt = time.time() - t0
        n_tok = args.gen * args.batch
        print(f"arch={cfg.name} mode=lockstep impl={impl} batch={args.batch} "
              f"{n_tok} tokens in {dt*1e3:.0f}ms ({n_tok/max(dt,1e-9):.1f} tok/s)")
        print("sample:", np.asarray(gen[0])[:12])
        return gen

    n_req = args.requests or 3 * args.batch
    reqs = _synthetic_requests(rng, n_req, args.prompt_len, args.gen,
                               cfg.vocab_size)
    draft = None
    if args.draft_depth > 0:
        from repro.spec.draft import build_draft_source
        draft = build_draft_source(
            args.draft_source, target_cfg=cfg, max_batch=args.batch,
            max_seq=max_seq, temperature=args.temperature,
            top_k=args.top_k, seed=args.sample_seed)
    engine = ContinuousBatchingEngine(
        model, params, max_batch=args.batch, max_seq=max_seq,
        eos_id=args.eos_id, kv_layout=args.kv_layout,
        block_size=args.block_size, num_blocks=args.kv_blocks,
        temperature=args.temperature, top_k=args.top_k,
        sample_seed=args.sample_seed, bucket_prompts=args.bucket_prompts,
        admission_policy=args.admission_policy,
        prefix_cache=not args.no_prefix_cache, swap_grace=args.swap_grace,
        draft_depth=args.draft_depth, draft_source=draft)
    t0 = time.time()
    finished = engine.run(reqs)
    dt = time.time() - t0
    tok_s = engine.tokens_out / max(dt, 1e-9)
    print(f"arch={cfg.name} mode=continuous impl={impl} kv={args.kv_layout} "
          f"slots={args.batch} requests={n_req} tokens={engine.tokens_out} "
          f"steps={engine.decode_steps} occupancy={engine.occupancy:.2f} "
          f"wall={dt*1e3:.0f}ms ({tok_s:.1f} tok/s)")
    if engine.spec_rounds:
        print(f"spec: depth={engine.draft_depth} source={args.draft_source} "
              f"accepted {engine.spec_accepted}/{engine.spec_drafted} drafts "
              f"({engine.spec_accepted / max(1, engine.spec_drafted):.2f})")
    if args.kv_layout == "paged":
        st = engine.stats()
        pool = st["pool"]
        print(f"pool: {pool['peak_blocks_in_use']}/{pool['num_blocks']} peak "
              f"blocks, peak KV {engine.kv_bytes(peak=True)/1e6:.2f}MB "
              f"(contig-equivalent slab would be fully resident)")
        if "prefix" in pool:
            pf = pool["prefix"]
            print(f"prefix: hit_rate={pf['hit_rate']:.2f} "
                  f"chunks run={st['prefill_chunks']} "
                  f"skipped={st['prefill_chunks_skipped']} "
                  f"cow={st['cow_copies']} "
                  f"swap out/in={st['swap_outs']}/{st['swap_ins']}")
    sample = finished[0].tokens[:12] if 0 in finished else []
    print("sample uid=0:", sample)
    if args.json_out:
        payload = obs.versioned({
            "arch": cfg.name, "impl": impl, "slots": args.batch,
            "requests": n_req, "tokens": engine.tokens_out,
            "steps": engine.decode_steps, "occupancy": round(engine.occupancy, 4),
            "wall_s": round(dt, 4), "tok_s": round(tok_s, 2),
            "stats": engine.stats(),
            "finished": {str(u): {"reason": f.reason, "n_tokens": len(f.tokens),
                                  "prompt_len": f.prompt_len}
                         for u, f in finished.items()},
        })
        with open(args.json_out, "w") as f:
            json.dump(obs.encode_record(payload), f, indent=1)
    if tel is not None:
        tel.save(args.telemetry_out)
        print(f"[obs] telemetry bundle -> {args.telemetry_out} "
              f"({len(tel.tracer.spans())} spans)")
    return finished


if __name__ == "__main__":
    main()
