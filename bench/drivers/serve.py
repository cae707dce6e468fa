"""Serving driver: a ``ContinuousBatchingEngine`` (paged KV, prefix cache,
Pallas decode, greedy) fed by the mix's requests and stepped by one host
loop.

Open-loop mixes (``poisson``, ``sessions``): each request is submitted once
it is due and timed from when it was due. A request's first token is
emitted while it is admitted inside ``step()``, so its first-token time is
the end of the step in which it became resident (or finished); every other
token's time is the end of the step that returned it. After the window the
engine serves the requests already due, for at most ``drain_s`` seconds
more, and no new ones. ``backlog`` mixes queue every request before the
window, which opens once every slot is full.
"""
from __future__ import annotations

import collections
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference, traffic, weights
from bench.drivers.common import (Check, Outcome, peak_bytes, program_config,
                                  program_params, quantile)

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _max_len(spec: dict) -> int:
    return int(spec["max"] if "max" in spec else spec["value"])


def max_seq(mix: dict) -> int:
    """The longest prompt plus the longest output the mix can send."""
    prompt = _max_len(mix["doc_len"]) + _max_len(mix["question_len"]) \
        if mix["arrivals"] == "sessions" else _max_len(mix["prompt_len"])
    return prompt + _max_len(mix["output_len"])


class Ledger:
    """Per-request token times and the work of traced steps."""

    def __init__(self, spec: counts.Spec, block: int):
        self.spec, self.block = spec, block
        self.due = {}                  # uid -> absolute due time
        self.prompt_len = {}
        self.times = collections.defaultdict(list)
        self.waiting = set()           # submitted, no first token yet
        self.work = {"decode": counts.ZERO, "prefill": counts.ZERO,
                     "paged_attention": counts.ZERO}

    def after_step(self, engine, emitted, t, skipped_before, record_work):
        resident = set(u for u in engine.slot_uid if u is not None)
        admitted = sorted(u for u in self.waiting
                          if u in resident or u in engine.finished)
        for u in admitted:
            self.waiting.discard(u)
            self.times[u].append(t)
        contexts = []
        for u, _ in emitted:
            self.times[u].append(t)
            contexts.append(self.prompt_len[u] + len(self.times[u]) - 1)
        if not record_work:
            return
        s = self.spec
        self.work["decode"] += counts.decode_step(s, contexts)
        self.work["paged_attention"] += counts.paged_decode_attention(s, contexts)
        skipped = engine.prefill_chunks_skipped - skipped_before
        for u in admitted:
            P = self.prompt_len[u]
            nb = -(-P // self.block)
            skip = min(nb, skipped)
            skipped -= skip
            chunks = [(nb - 1, False)] if skip == nb else \
                [(c, True) for c in range(skip, nb)]
            for c, write in chunks:
                self.work["prefill"] += counts.prefill_chunk(
                    s, c * self.block, min(self.block, P - c * self.block), write)


def run(ctx) -> Outcome:
    from repro.launch.serve import ContinuousBatchingEngine, Request
    from repro.models.registry import build_model

    cfg, mix, seed, spec = ctx.cfg, ctx.mix, ctx.seed, ctx.spec
    eng_cfg = cfg["engine"]
    block = eng_cfg["block_size"]
    t0 = time.perf_counter()
    model = build_model(program_config(cfg), impl=cfg["attention"])
    engine = ContinuousBatchingEngine(
        model, program_params(weights.make(spec, seed), model),
        max_batch=eng_cfg["max_batch"], max_seq=max_seq(mix),
        kv_layout="paged", block_size=block, num_blocks=eng_cfg["num_blocks"],
        cache_dtype=DTYPES[cfg["precision"]["kv_cache"]])
    # one warm-up request a step: each step then ships a single dirty table
    # row (the row update, not the bulk upload), and the repeated prompt
    # arrives while its donor is still resident (a shared block to copy)
    for r in traffic.warmup_requests(mix, spec.vocab, seed, block, max_seq(mix)):
        engine.submit(Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new))
        engine.step()
    while engine.has_work:
        engine.step()
    engine.finished.clear()
    reqs = traffic.requests(mix, spec.vocab, seed, ctx.seconds)
    by_uid = {r.uid: r for r in reqs}
    led = Ledger(spec, block)
    backlog = mix["arrivals"] == "backlog"
    if backlog:
        for r in reqs:
            engine.submit(Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new))
            led.prompt_len[r.uid] = len(r.prompt)
            led.waiting.add(r.uid)
        while engine.queue and None in engine.slot_uid:
            emitted = engine.step()
            led.after_step(engine, emitted, time.perf_counter(), 0, False)
    setup_s = time.perf_counter() - t0

    pending = collections.deque(r for r in reqs if not backlog)
    c0 = (engine.prefill_chunks, engine.prefill_chunks_skipped, engine.decode_steps)
    tokens0 = sum(len(v) for v in led.times.values())
    tracer = ctx.tracer
    trace_s = mix["trace_seconds"]
    trace_at = max(0.0, (ctx.seconds - trace_s) / 2)
    lateness = []
    ctx.compiles.open()
    start = time.perf_counter()
    while True:
        now = time.perf_counter()
        el = now - start
        if el >= ctx.seconds:
            break
        if tracer is not None:
            if not tracer.running and tracer.started == 0.0 and el >= trace_at:
                tracer.start()
            elif tracer.running and now - tracer.started >= trace_s:
                tracer.stop()
        while pending and pending[0].due_s <= el:
            r = pending.popleft()
            with jax.profiler.TraceAnnotation("bench.submit"):
                engine.submit(Request(uid=r.uid, prompt=r.prompt,
                                      max_new_tokens=r.max_new))
            led.due[r.uid] = start + r.due_s
            led.prompt_len[r.uid] = len(r.prompt)
            led.waiting.add(r.uid)
            lateness.append(el - r.due_s)
        if not engine.has_work:
            nxt = pending[0].due_s if pending else ctx.seconds
            with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                time.sleep(max(0.0, min(nxt, ctx.seconds) - el))
            continue
        sk = engine.prefill_chunks_skipped
        with jax.profiler.TraceAnnotation("bench.step"):
            emitted = engine.step()
        led.after_step(engine, emitted, time.perf_counter(), sk,
                       tracer is not None and tracer.running)
    end = time.perf_counter()
    if tracer is not None and tracer.running:
        tracer.stop()
    ctx.compiles.close()
    window_s = end - start
    counters = {"prefill_chunks": engine.prefill_chunks - c0[0],
                "prefill_chunks_skipped": engine.prefill_chunks_skipped - c0[1],
                "decode_steps": engine.decode_steps - c0[2]}
    tokens = sum(len(v) for v in led.times.values()) - tokens0

    waiting_at_end = len(engine.queue)
    drain_end = time.perf_counter() + \
        (mix["drain_s"] if ctx.check and not backlog else 0.0)
    while engine.has_work and time.perf_counter() < drain_end:
        emitted = engine.step()
        led.after_step(engine, emitted, time.perf_counter(), 0, False)
    mem = peak_bytes(ctx.device)

    e2e = {"setup_s": setup_s, "serve_tokens_per_s": tokens / window_s}
    problems = []
    if not backlog:
        ttft = [led.times[u][0] - led.due[u] if led.times[u] else math.inf
                for u in led.due]
        gaps = [b - a for ts in led.times.values()
                for a, b in zip(ts, ts[1:]) if b <= end]
        e2e["ttft_p75_s"] = quantile(ttft, 0.75)
        e2e["itl_p99_ms"] = quantile(gaps, 0.99) * 1e3
        unserved = [u for u in led.due if u not in engine.finished]
        if unserved:
            problems.append(f"{len(unserved)} due requests never finished")
        attempted = len(led.due)
        ctx.note(f"generator: {len(lateness)} requests submitted, lateness "
                 f"max {max(lateness, default=0.0):.4f} s, p99 "
                 f"{quantile(lateness, 0.99) if lateness else 0.0:.4f} s; "
                 f"{len(gaps)} token gaps, {len(ttft)} first tokens")
    else:
        attempted = len([u for u in led.times if led.times[u]])
        unserved = []
    failed = len(engine.rejected) + len(unserved)
    finished = {u: len(by_uid[u].prompt) + len(f.tokens)
                for u, f in engine.finished.items()
                if u in by_uid and (not backlog or led.times[u][-1] <= end)}
    served = {u: (by_uid[u].prompt, list(engine.finished[u].tokens))
              for u in traffic.check_sample(finished, mix["check_requests"], seed)}
    del engine, model
    gc.collect()
    record = {"counters": counters, "work": led.work, "window_s": window_s,
              "tokens": tokens, "waiting_at_end": waiting_at_end,
              "ttft_by_due": sorted((led.due[u] - start, led.times[u][0] - led.due[u])
                                    for u in led.due if led.times[u])}
    if not ctx.check:
        return Outcome(e2e=e2e, attempted=attempted, failed=failed, checks=[],
                       memory_peak_bytes=mem, record=record)

    gap, n_tok, n_miss, ctrl = _compare(cfg, spec, seed, served, ctx.control)
    record["control"] = ctrl
    ctx.note(f"check: {len(served)} requests, {n_tok} served tokens, "
             f"{n_miss} not the reference's best")
    if not served:
        problems.append("no finished request to compare")
    return Outcome(
        e2e=e2e, attempted=attempted, failed=failed,
        checks=[Check("logit_gap", gap, ctx.limits["logit_gap"])],
        memory_peak_bytes=mem,
        record=record,
        problems=problems)


def _compare(cfg, spec, seed, served, control=False):
    """Widest logit gap of the served tokens under the plain reference; with
    ``control``, also the same readings for the tokens the bfloat16 control
    puts first at the same positions. Returns the gap, the number of served
    tokens, how many of them were not the reference's best, and the
    control's readings (or None)."""
    if not served:
        return math.inf, 0, 0, None
    w = weights.make(spec, seed)
    scorer = reference.Scorer(cfg)
    worst, n_tok, n_miss, refs = 0.0, 0, 0, []
    for prompt, toks in served.values():
        ref = scorer.served_logits(w, prompt, toks)
        g, m = reference.logit_gap(ref, toks)
        worst, n_tok, n_miss = max(worst, g), n_tok + len(toks), n_miss + m
        if control:
            refs.append(np.asarray(ref))
    if not control:
        return worst, n_tok, n_miss, None
    # the control's weights are bfloat16: converted leaf by leaf so that
    # the float32 copy is freed as it goes
    for k in sorted(w):
        w[k] = w[k].astype(jnp.bfloat16)
    low = reference.Scorer(cfg, jnp.bfloat16, "default")
    ctrl, c_miss = 0.0, 0
    for ref, (prompt, toks) in zip(refs, served.values()):
        picks = np.asarray(jnp.argmax(low.served_logits(w, prompt, toks), -1))
        g, m = reference.logit_gap(ref, picks)
        ctrl, c_miss = max(ctrl, g), c_miss + m
    return worst, n_tok, n_miss, {"logit_gap": ctrl, "miss_share": c_miss / n_tok,
                                  "program_miss_share": n_miss / n_tok}
