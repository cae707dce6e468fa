"""Training driver: one ``TrainSession`` with Adam and the Pallas flash
kernels, stepped by a ``SwanRuntime`` one tick at a time.

Set-up builds the session around the benchmark's weights, runs its first
three steps (compiling the step) and reads what the check needs from their
state. The window then keeps stepping the same session, on fresh batches,
until ``seconds`` have passed; every step ends in the session's blocking
read of its loss. After the window the session is freed and the plain
reference repeats the first three steps for the check.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic, weights
from bench.drivers.common import (Check, Outcome, canonical, peak_bytes,
                                  program_config, program_params)

CHECKED_STEPS = 3


def _norms(tree: dict) -> dict:
    f = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                           for k, v in t.items()})
    return {k: float(v) for k, v in f(tree).items()}


def _leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def _reference(cfg, spec, seed, batches, dtype=jnp.float32, precision="highest"):
    """The plain reference's losses, first-gradient norms and change norms
    over ``batches`` from the seed's weights."""
    ref = reference.train(cfg, weights.make(spec, seed), batches,
                          dtype=dtype, precision=precision)
    ref["delta_norms"] = reference.delta_norms(ref.pop("weights"),
                                               weights.make(spec, seed))
    return ref


def _gaps(prog: dict, ref: dict, moved) -> dict:
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": _leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "update_gap": _leaf_gap(prog["delta_norms"], ref["delta_norms"], moved),
    }


def run(ctx) -> Outcome:
    from repro.engine.rungs import Rung
    from repro.engine.runtime import SwanRuntime
    from repro.engine.session import TrainSession
    from repro.models.registry import build_model
    from repro.optim.optimizers import adam

    cfg, mix, seed, spec = ctx.cfg, ctx.mix, ctx.seed, ctx.spec
    o = cfg["optimizer"]
    t0 = time.perf_counter()
    pcfg = program_config(cfg)
    opt = adam(b1=o["b1"], b2=o["b2"], eps=o["eps"])
    rung = Rung(name="bench", attn_impl=cfg["attention"])
    params = program_params(weights.make(spec, seed), build_model(pcfg))
    state = {"params": params, "opt": opt.init(params), "err": (),
             "step": jnp.zeros((), jnp.int32)}
    del params
    session = TrainSession(
        pcfg, [rung], optimizer=opt, lr=o["lr"], adaptive=False, verbose=False,
        batch_fn=lambda step: traffic.train_batch(mix, spec.vocab, seed, step))
    session.bind(1 << 40, state=state, rng_seed=0)
    del state
    runtime = SwanRuntime([session])
    tick = 0

    def step():
        nonlocal tick
        with jax.profiler.TraceAnnotation("bench.train_step"):
            runtime.run(tick + 1, start=tick)
        tick += 1

    step()
    b1 = o["b1"]
    grad_prog = {k: v / (1 - b1) for k, v in
                 _norms(canonical(session.result().state["opt"]["m"])).items()}
    for _ in range(CHECKED_STEPS - 1):
        step()
    delta_prog = reference.delta_norms(
        canonical(session.result().state["params"]), weights.make(spec, seed))
    loss_prog = list(session.result().losses[:CHECKED_STEPS])
    setup_s = time.perf_counter() - t0

    ctx.compiles.open()
    tracer = ctx.tracer
    start = time.perf_counter()
    n = 0
    traced = False
    while True:
        now = time.perf_counter()
        if now - start >= ctx.seconds:
            break
        if tracer is not None and not traced and n == ctx.mix.get("trace_after_steps", 2):
            tracer.start()
            traced = True
        step()
        n += 1
        if traced and tracer.running and \
                time.perf_counter() - tracer.started >= mix["trace_seconds"]:
            tracer.stop()
    end = time.perf_counter()
    if tracer is not None and tracer.running:
        tracer.stop()
    ctx.compiles.close()
    window_s = end - start
    losses = session.result().losses
    mem = peak_bytes(ctx.device)
    bad = [x for x in losses if not np.isfinite(x)]

    del session, runtime, rung
    gc.collect()
    batches = [traffic.train_batch(mix, spec.vocab, seed, i)
               for i in range(CHECKED_STEPS)]
    ref = _reference(cfg, spec, seed, batches)
    med = float(np.median(list(ref["grad_norms"].values())))
    moved = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * med}
    prog = {"losses": loss_prog, "grad_norms": grad_prog, "delta_norms": delta_prog}
    gaps = _gaps(prog, ref, moved)
    checks = [Check(k, v, ctx.limits[k]) for k, v in gaps.items() if k in ctx.limits]
    control = None
    if ctx.control:
        # the reference in bfloat16 in the program's place, and the
        # reference fed half of each batch (the mean over the rest)
        low = _reference(cfg, spec, seed, batches, jnp.bfloat16, "default")
        half = _reference(cfg, spec, seed, [{"tokens": b["tokens"][:len(b["tokens"]) // 2]}
                                            for b in batches])
        control = {"control": _gaps(low, ref, moved), "half_batch": _gaps(half, ref, moved)}
    tokens = mix["batch"] * mix["seq"]
    ctx.note(f"train: {n} steps in {window_s:.4f} s window; losses program "
             f"{loss_prog} reference {ref['losses']} (loss_gap {gaps['loss_gap']!r}, "
             f"not compared); leaves compared for the update {len(moved)} of "
             f"{len(ref['grad_norms'])}")
    return Outcome(
        e2e={"train_tokens_per_s": n * tokens / window_s, "setup_s": setup_s},
        attempted=n + CHECKED_STEPS, failed=len(bad), checks=checks,
        memory_peak_bytes=mem,
        record={"steps": n, "window_s": window_s, "tokens_per_step": tokens,
                "batch": mix["batch"], "seq": mix["seq"], "control": control},
        problems=[f"{len(bad)} non-finite losses"] if bad else [])
