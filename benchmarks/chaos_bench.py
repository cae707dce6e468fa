"""Chaos robustness bench: SLO attainment under injected faults.

Runs the same train + serve + foreground workload on one SwanRuntime three
times over a shared, seeded chaos schedule (engine/chaos.py — device loss,
KV-pool pressure, torn checkpoints, thermal spikes, latency spikes,
foreground bursts):

- ``faultfree``        — no chaos; the parity and attainment baseline.
- ``chaos_serialize``  — faults on, the engine's old implicit admission
                         behavior (head-of-line requests wait out pool
                         pressure; nothing is ever refused).
- ``chaos_shed``       — same faults, ``admission_policy="shed"``: requests
                         that cannot get KV blocks now are rejected with a
                         retry-after hint, so the requests that ARE admitted
                         keep their token latency.

Observed serve latency is modeled deterministically as
``rung estimate x thermal trace x chaos spike x (1 + c·queue_depth)`` — the
queue term is what load shedding buys back.

Gates (CI):
- every injected fault class is applied and every run completes inside one
  process (recovery never needs a restart);
- the training step sequence is contiguous in every scenario — pause/resume
  and torn-checkpoint fallback never skip or redo an optimizer step;
- every foreground pause resumes at exactly the pre-pause step;
- finished requests emit byte-identical token streams vs the fault-free run
  (greedy decode parity survives chaos);
- shed-policy SLO attainment >= serialize-policy attainment.

Writes BENCH_slo.json. The scenarios run in a subprocess with 8 forced host
devices so device-loss faults exercise a real remesh.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SEED = 7
SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
TRAIN_EST = 0.1
SERVE_EST = 0.1
SLO_P99_S = 0.30      # meets quiet traffic; queue growth + spikes break it
QUEUE_COEF = 0.04     # latency penalty per queued request
DEADLINE_STEPS = 30   # queued-admission deadline (engine steps)
N_REQUESTS = 20
GEN_TOKENS = 8


# ---------------------------------------------------------------------------
# inner: the actual scenarios (run under forced 8-device host)
# ---------------------------------------------------------------------------


def _tiny_cfg(name):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=name, family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                       tie_embeddings=True, source="benchmarks/chaos_bench.py")


def _train_job(trace, ticks):
    from repro.engine.jobs import trace_latency_fn
    from repro.engine.rungs import default_rung_ladder
    from repro.engine.session import TrainSession
    from repro.launch.train import make_batch_fn
    from repro.optim.optimizers import sgd
    from repro.runtime.elastic import ElasticController

    cfg = _tiny_cfg("chaos-train-tiny")
    elastic = ElasticController(total_devices=8)
    rungs = default_rung_ladder(batch=8, microbatch=1, attn_impl="naive",
                                include_bf16=False)
    for r in rungs:
        r.latency_estimate_s = TRAIN_EST * r.rel_latency
    ses = TrainSession(cfg, rungs, optimizer=sgd(), lr=0.05,
                       batch_fn=make_batch_fn(cfg, 8, 16), elastic=elastic,
                       latency_fn=trace_latency_fn(trace), adaptive=True,
                       upgrade_patience=4, verbose=False, name="train")
    return ses.bind(ticks), elastic


def _serve_job(trace, chaos, policy):
    import jax
    import numpy as np
    from repro.engine.jobs import ServeJob, ServeRung
    from repro.launch.serve import ContinuousBatchingEngine, Request
    from repro.models.registry import build_model

    cfg = _tiny_cfg("chaos-serve-tiny")
    model = build_model(cfg, impl="naive")
    params = model.init(jax.random.PRNGKey(0))
    slots, block = 4, 4
    # a deliberately tight pool: 4 residents' worst case (4 blocks each:
    # 6-token prompt + 8-token budget) just fits 17 usable blocks, so a
    # chaos hold of a couple of blocks pushes admission into pressure
    engine = ContinuousBatchingEngine(
        model, params, max_batch=slots, max_seq=48, kv_layout="paged",
        block_size=block, num_blocks=18, admission_policy=policy)
    rng = np.random.default_rng(SEED)
    reqs = [Request(uid=i, prompt=rng.integers(0, 64, 6).astype(np.int32),
                    max_new_tokens=GEN_TOKENS, deadline_steps=DEADLINE_STEPS)
            for i in range(N_REQUESTS)]

    def lat_fn(step, rung, dt):
        eff = trace.effective_slowdown(step, rung.interference_sensitivity)
        spike = chaos.latency_multiplier(step) if chaos is not None else 1.0
        queue = 1.0 + QUEUE_COEF * len(engine.queue)
        return rung.latency_estimate_s * eff * spike * queue

    rels = (1.0, 1.4, 1.9)
    sens = (1.0, 0.4, 0.16)
    caps = (None, 2, 1)
    rungs = [ServeRung(name=n, slot_cap=c, interference_sensitivity=s,
                       rel_latency=r, latency_estimate_s=SERVE_EST * r)
             for n, c, s, r in zip(("serve-full", "serve-capped",
                                    "serve-lean"), caps, sens, rels)]
    return ServeJob(engine, reqs, rungs=rungs, latency_fn=lat_fn,
                    adaptive=True, upgrade_patience=4, name="serve",
                    slo_p99_s=SLO_P99_S, slo_window=48, slo_min_samples=8)


def _scenario(name, ticks, *, policy, with_chaos):
    from repro.engine.chaos import ChaosInjector
    from repro.engine.events import ThermalTrace
    from repro.engine.jobs import ForegroundAppJob
    from repro.engine.runtime import SwanRuntime

    trace = ThermalTrace(heat_rate=0.03, cool_rate=0.02, slowdown=2.0)
    chaos = ChaosInjector.random(SEED, ticks, events_per_kind=3) \
        if with_chaos else None
    train, elastic = _train_job(trace, ticks)
    serve = _serve_job(trace, chaos, policy)
    # one scripted burst in every scenario so even fault-free exercises the
    # pause -> checkpoint -> resume path; chaos injects extra fg_burst events
    fg = ForegroundAppJob(bursts=[(ticks // 3, ticks // 3 + 4)])
    rt = SwanRuntime([train, serve, fg], trace=trace, elastic=elastic,
                     chaos=chaos)
    res = rt.run(ticks)

    train_steps = [s.step for s in train.timeline.steps]
    pauses = [m.step for m in train.timeline.migrations if m.reason == "pause"]
    resumes = [m.step for m in train.timeline.migrations
               if m.reason == "resume"]
    finished = {int(u): list(f.tokens)
                for u, f in serve.engine.finished.items()}
    stats = serve.engine.stats()
    return {
        "name": name,
        "policy": policy,
        "chaos": chaos.to_json() if chaos is not None else None,
        "preemptions": res.preemptions,
        "train_steps": train_steps,
        "train_final_step": train_steps[-1] + 1 if train_steps else 0,
        "pauses": pauses,
        "resumes": resumes,
        "finished": finished,
        "slo": serve.slo_stats(),
        "shed": stats["shed"],
        "timeouts": stats["timeouts"],
        "rejected": stats["rejected"],
        "migrations": len(res.timeline.migrations),
        "work": {k: round(v, 2) for k, v in res.work.items()},
    }


def _contiguous(steps):
    return all(b - a == 1 for a, b in zip(steps, steps[1:]))


def inner(ticks: int, out_path: str, trace_path: str = "") -> None:
    # telemetry on for the whole chaos run: the exported Chrome trace is the
    # CI artifact that shows ticks/steps/decodes interleaving under faults
    from repro import obs
    tel = obs.enable() if trace_path else None
    scenarios = [
        _scenario("faultfree", ticks, policy="serialize", with_chaos=False),
        _scenario("chaos_serialize", ticks, policy="serialize",
                  with_chaos=True),
        _scenario("chaos_shed", ticks, policy="shed", with_chaos=True),
    ]
    base = scenarios[0]
    payload = {"ticks": ticks, "seed": SEED, "slo_p99_s": SLO_P99_S,
               "scenarios": {}, "gates": {}}
    for sc in scenarios:
        common = sorted(set(sc["finished"]) & set(base["finished"]))
        parity = all(sc["finished"][u] == base["finished"][u]
                     for u in common)
        payload["scenarios"][sc["name"]] = {
            **{k: v for k, v in sc.items()
               if k not in ("train_steps", "finished")},
            "train_contiguous": _contiguous(sc["train_steps"]),
            "resume_exact": sc["resumes"] == sc["pauses"][:len(sc["resumes"])],
            "finished_requests": len(sc["finished"]),
            "parity_common": len(common),
            "token_parity": parity,
        }
    g = payload["gates"]
    chaos_kinds = set()
    for name in ("chaos_serialize", "chaos_shed"):
        chaos_kinds.update(payload["scenarios"][name]
                           .get("chaos", {}).get("applied", []))
    g["all_fault_kinds_applied"] = sorted(chaos_kinds)
    g["train_contiguous"] = all(
        s["train_contiguous"] for s in payload["scenarios"].values())
    g["resume_exact"] = all(
        s["resume_exact"] and s["pauses"]
        for s in payload["scenarios"].values())
    g["token_parity"] = all(
        s["token_parity"] for s in payload["scenarios"].values())
    att = {n: payload["scenarios"][n]["slo"]["attainment"]
           for n in payload["scenarios"]}
    g["attainment"] = att
    g["shed_ge_serialize"] = (
        att["chaos_shed"] is not None and
        att["chaos_serialize"] is not None and
        att["chaos_shed"] >= att["chaos_serialize"])
    g["pressure_exercised"] = \
        payload["scenarios"]["chaos_shed"]["shed"] > 0
    if tel is not None:
        payload["trace_spans"] = len(tel.tracer.spans())
        tel.tracer.save_chrome_trace(trace_path)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)


# ---------------------------------------------------------------------------
# outer: subprocess driver + CI gates
# ---------------------------------------------------------------------------


def run(fast: bool = True, json_path: str = "BENCH_slo.json",
        trace_path: str = "BENCH_chaos_trace.json"):
    if SRC not in sys.path:  # direct `python benchmarks/chaos_bench.py` runs
        sys.path.insert(0, SRC)
    ticks = 48 if fast else 96
    print("chaos: CPU-only recovery gate, run on 8 virtual CPU devices "
          "(JAX_PLATFORMS=cpu); none of its numbers is a chip result",
          flush=True)
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--inner",
         "--ticks", str(ticks), "--out", json_path,
         "--trace-out", trace_path],
        env=env, capture_output=True, text=True, timeout=1800)
    us = (time.perf_counter() - t0) * 1e6
    assert proc.returncode == 0, \
        f"chaos scenarios crashed (recovery failed?):\n{proc.stderr[-4000:]}"
    with open(json_path) as f:
        payload = json.load(f)
    g = payload["gates"]

    from repro.engine.chaos import KINDS
    missing = set(KINDS) - set(g["all_fault_kinds_applied"])
    assert not missing, f"fault classes never applied: {sorted(missing)}"
    assert g["train_contiguous"], \
        "training skipped or redid an optimizer step under chaos"
    assert g["resume_exact"], \
        "a foreground pause did not resume at the pre-pause step"
    assert g["token_parity"], \
        "finished requests diverged from the fault-free token streams"
    assert g["pressure_exercised"], \
        "pool pressure never forced a shed — the chaos schedule is toothless"
    assert g["shed_ge_serialize"], \
        f"shed must not lose SLO attainment to serialize: {g['attainment']}"

    rows = []
    for name, sc in payload["scenarios"].items():
        att = sc["slo"]["attainment"]
        rows.append((f"chaos/{name}/slo_attainment", us,
                     f"{att};shed={sc['shed']};timeouts={sc['timeouts']};"
                     f"preemptions={sc['preemptions']}"))
    rows.append(("chaos/faults_applied", us,
                 "+".join(g["all_fault_kinds_applied"])))
    if trace_path and os.path.exists(trace_path):
        with open(trace_path) as f:
            trace = json.load(f)
        n_x = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
        assert n_x > 0, "chaos run recorded no spans in the Chrome trace"
        rows.append(("chaos/trace_spans", us, str(n_x)))
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--ticks", type=int, default=48)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_slo.json")
    ap.add_argument("--trace-out", default="BENCH_chaos_trace.json",
                    help="Chrome-trace artifact from the instrumented chaos "
                         "run ('' disables)")
    args = ap.parse_args()
    if args.inner:
        inner(args.ticks, args.out, args.trace_out)
    else:
        for name, us, derived in run(fast=not args.full, json_path=args.out,
                                     trace_path=args.trace_out):
            print(f"{name},{us:.1f},{derived}")
