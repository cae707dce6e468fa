"""Chip smoke test: train and serve granite-3-2b at its published widths on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip: a train phase, then a serve phase
    python chip_smoke.py --four-chips   # only the train step on a 2x2 mesh vs one chip

Every phase goes through the entry points a user calls (``TrainSession``,
``ContinuousBatchingEngine``) with the Pallas kernels named, never ``auto``.
Weights and data are random, made from ``--seed``. A failed check raises, so
the script exits non-zero; with no TPU it exits non-zero before any phase.
The last line of standard output is one JSON object naming the device.

Times and memory printed along the way are informational only: they are not
benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.engine.rungs import Rung  # noqa: E402
from repro.engine.session import TrainSession  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import ContinuousBatchingEngine, Request  # noqa: E402
from repro.launch.steps import (build_paged_decode_step,  # noqa: E402
                                init_train_state)
from repro.launch.train import make_batch_fn  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim.optimizers import adam  # noqa: E402
from repro.runtime.elastic import ElasticController  # noqa: E402

ARCH = "granite-3-2b"
# Train: every published width, depth cut so that one f32 Adam step fits a
# v5e's 16 GB. Compiled for a described v5e, this step needs 4.13 GB of
# donated state and 9.50 GB of temporaries; 8 layers at this batch do not fit.
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 4, 2, 2048, 5, 3e-4
MESH_STEPS = 3
MESH_LOSS_RTOL = 1e-3  # 4-chip vs 1-chip loss, f32 with another reduction order
# Serve: all 40 layers in f32 (10.1 GB of weights).
SERVE_LAYERS, SERVE_SLOTS, SERVE_REQUESTS = 40, 4, 8
PROMPT_LEN, GEN_LEN, BLOCK_SIZE = 512, 32, 16
# pallas vs naive paged decode, both at f32 matmul precision: the kernel's
# online softmax only reorders f32 sums
LOGIT_ATOL = 1e-3


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips; JAX found "
                         f"{len(devices)}")
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    return devices


def peak_hbm_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def train_config(layers: int):
    return dataclasses.replace(get_config(ARCH), n_layers=layers)


def run_train(cfg, *, steps: int, batch: int, seq: int, seed: int,
              mesh_shape=None, elastic=None):
    """``steps`` Adam steps of a ``TrainSession`` with the Pallas flash
    kernels. Returns (losses, params, compile seconds, the compiled step's
    HLO text, each step's seconds); compile and HLO only without a mesh."""
    rung = Rung(name="smoke", attn_impl="pallas", mesh_shape=mesh_shape)
    opt = adam()
    batch_fn = make_batch_fn(cfg, batch, seq, seed=seed)
    state, hlo, compile_s = None, "", float("nan")
    if mesh_shape is None:
        # compile ahead of the session, which then reuses the executable;
        # the session's own state init (same seed) places it on the mesh
        state = init_train_state(rung.build_model(cfg), opt,
                                 jax.random.PRNGKey(seed))
        t0 = time.perf_counter()
        compiled = rung.jitted_step(cfg, opt, lr=TRAIN_LR).lower(
            state, batch_fn(0)).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
    session = TrainSession(cfg, [rung], optimizer=opt, lr=TRAIN_LR,
                           batch_fn=batch_fn, elastic=elastic,
                           adaptive=False, verbose=False)
    result = session.run(steps, state=state, rng_seed=seed)
    return (result.losses, result.state["params"], compile_s, hlo,
            [s.latency_s for s in result.timeline.steps])


def train_phase(device, seed: int) -> None:
    cfg = train_config(TRAIN_LAYERS)
    print(f"[train] {ARCH}: n_layers={cfg.n_layers} (of 40) d_model="
          f"{cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim="
          f"{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; batch "
          f"{TRAIN_BATCH} x seq {TRAIN_SEQ}, adam, attn=pallas, f32",
          flush=True)
    losses, params, compile_s, hlo, step_s = run_train(
        cfg, steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed)
    del params
    print(f"[train] losses {[round(x, 4) for x in losses]}", flush=True)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite train loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train loss did not fall: {losses}")
    # one fwd, one dq and one dkv Mosaic kernel in the scanned layer body
    n_kernels = hlo.count("tpu_custom_call")
    if n_kernels < 3:
        raise AssertionError(f"compiled train step holds {n_kernels} "
                             f"tpu_custom_call ops; expected fwd, dq, dkv")
    print(f"[train] informational, not benchmark metrics: compile "
          f"{compile_s:.2f} s, first step {step_s[0]:.4f} s, steady step "
          f"{float(np.median(step_s[1:])):.4f} s (median of "
          f"{TRAIN_STEPS - 1}), tpu_custom_call ops {n_kernels}, peak HBM "
          f"{peak_hbm_gb(device)}", flush=True)


def serve_phase(device, seed: int) -> None:
    cfg = dataclasses.replace(get_config(ARCH), n_layers=SERVE_LAYERS)
    print(f"[serve] {ARCH}: n_layers={cfg.n_layers} (of 40), f32 weights, "
          f"paged KV (block {BLOCK_SIZE}), attn=pallas; {SERVE_REQUESTS} "
          f"requests, prompt {PROMPT_LEN}, gen {GEN_LEN}, {SERVE_SLOTS} "
          f"slots", flush=True)
    model = build_model(cfg, impl="pallas")
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    max_seq = PROMPT_LEN + GEN_LEN
    engine = ContinuousBatchingEngine(
        model, params, max_batch=SERVE_SLOTS, max_seq=max_seq,
        kv_layout="paged", block_size=BLOCK_SIZE)
    rng = np.random.default_rng(seed)
    for uid in range(SERVE_REQUESTS):
        engine.submit(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab_size, PROMPT_LEN,
                                         dtype=np.int32),
            max_new_tokens=GEN_LEN))
    t0 = time.perf_counter()
    engine.step()  # admits (prefills) a full set of slots, decodes once
    first_s = time.perf_counter() - t0

    # pallas vs naive on the live paged cache, before the next step
    args = (engine.params, engine.cache, jnp.asarray(engine.tokens),
            jnp.asarray(engine.cache_len), engine._dev_tables)
    hlo = engine._decode.lower(*args).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("compiled decode step holds no tpu_custom_call")
    with jax.default_matmul_precision("highest"):
        logits = {}
        for impl in ("pallas", "naive"):
            step = build_paged_decode_step(build_model(cfg, impl=impl),
                                           donate=False)
            logits[impl] = np.asarray(step(*args)[1])
    diff = float(np.max(np.abs(logits["pallas"] - logits["naive"])))
    print(f"[serve] paged decode pallas vs naive: max |logit diff| "
          f"{diff:.3e} (tolerance {LOGIT_ATOL:.0e}; logits up to "
          f"{float(np.max(np.abs(logits['naive']))):.3f})", flush=True)
    if not diff <= LOGIT_ATOL:
        raise AssertionError(f"pallas paged decode differs from naive by "
                             f"{diff:.3e} > {LOGIT_ATOL:.0e}")
    del logits

    t0 = time.perf_counter()
    while engine.has_work:
        engine.step()
    rest_s = time.perf_counter() - t0
    done = engine.finished
    short = {u: (len(f.tokens), f.reason) for u, f in done.items()
             if len(f.tokens) != GEN_LEN}
    if len(done) != SERVE_REQUESTS or short:
        raise AssertionError(f"{len(done)}/{SERVE_REQUESTS} requests "
                             f"finished; short budgets: {short}")
    print(f"[serve] {len(done)}/{SERVE_REQUESTS} requests finished with "
          f"{GEN_LEN} tokens each; {engine.decode_steps} decode steps, "
          f"{engine.prefill_chunks} prefill chunks", flush=True)
    print(f"[serve] informational, not benchmark metrics: first step "
          f"(compiles + prefill) {first_s:.2f} s, remaining steps "
          f"{rest_s:.2f} s, peak HBM {peak_hbm_gb(device)}", flush=True)


def four_chip_phase(devices, seed: int) -> None:
    cfg = train_config(TRAIN_LAYERS)
    print(f"[mesh] {ARCH} n_layers={cfg.n_layers}, batch {TRAIN_BATCH} x seq "
          f"{TRAIN_SEQ}, {MESH_STEPS} adam steps: 1 chip vs a (data=2, "
          f"model=2) mesh", flush=True)
    one, params, *_ = run_train(cfg, steps=MESH_STEPS, batch=TRAIN_BATCH,
                                seq=TRAIN_SEQ, seed=seed)
    del params
    gc.collect()
    t0 = time.perf_counter()
    four, params, *_ = run_train(
        cfg, steps=MESH_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed,
        mesh_shape=(2, 2), elastic=ElasticController(total_devices=4))
    mesh_s = time.perf_counter() - t0
    device_sets = {len(p.sharding.device_set)
                   for p in jax.tree_util.tree_leaves(params)}
    rel = max(abs(a - b) / abs(a) for a, b in zip(one, four))
    print(f"[mesh] losses 1 chip {[round(x, 5) for x in one]}, 4 chips "
          f"{[round(x, 5) for x in four]}; max relative diff {rel:.2e} "
          f"(tolerance {MESH_LOSS_RTOL:.0e}); param device sets "
          f"{sorted(device_sets)}", flush=True)
    if not all(np.isfinite(four)) or not rel <= MESH_LOSS_RTOL:
        raise AssertionError(f"4-chip losses {four} disagree with 1-chip "
                             f"{one}")
    if device_sets != {4}:
        raise AssertionError(f"params span device sets {device_sets}, "
                             f"expected 4 devices each")
    print(f"[mesh] informational, not benchmark metrics: mesh run "
          f"{mesh_s:.2f} s for {MESH_STEPS} steps (compile included)",
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh train step and its "
                         "1-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = require_tpu(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase(devices, args.seed)
    else:
        train_phase(devices[0], args.seed)
        gc.collect()
        serve_phase(devices[0], args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
