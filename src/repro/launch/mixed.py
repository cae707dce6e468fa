"""Mixed-workload driver: training and serving co-tenant on one SoC.

Swan's premise is that workloads share the device; this CLI is the smallest
end-to-end demonstration: one ``TrainSession`` (personalization training in
the background) and one ``ServeJob`` (interactive decode) under a single
``SwanRuntime`` arbiter. The shared ThermalTrace integrates the **summed**
power draw of both jobs — training alone may never trip the throttle, but
training *plus* serving does, and the arbiter decides who relinquishes:
the job whose next rung frees the most contended resource per unit of
goodput lost (priority-weighted). An optional energy budget
(``core.energy.EnergyLoan``) additionally walks jobs toward low-power rungs
once the borrowed battery would cross the critical level.

Usage:
  PYTHONPATH=src python -m repro.launch.mixed --arch llama3.2-1b --reduced \
      --ticks 40 --batch 8 --seq 64 --slots 4 --requests 16 \
      --thermal-trace 0.2:0.25:3.0 --timeline-out /tmp/mixed.json
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.core.energy import EnergyLoan
from repro.engine.chaos import ChaosInjector
from repro.engine.events import (ChargingTrace, InterferenceTrace,
                                 ThermalTrace)
from repro.engine.jobs import (ForegroundAppJob, ServeJob,
                               default_serve_ladder)
from repro.engine.runtime import SwanRuntime
from repro.engine.rungs import default_rung_ladder
from repro.engine.session import TrainSession
from repro.kernels.backend import auto_attn_impl, auto_decode_impl
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import ContinuousBatchingEngine
from repro.launch.serve import _synthetic_requests
from repro.launch.train import make_batch_fn
from repro.models.registry import build_model
from repro.optim.compression import Compressor
from repro.optim.optimizers import adam, sgd


def build_jobs(args):
    """Job list from the CLI namespace: [train, serve] plus a foreground
    app when ``--fg-burst`` is given. (The arbitration benchmark builds its
    own latency-simulated jobs; this is the real-compute construction
    path.)"""
    cfg_t = get_config(args.arch)
    cfg_s = get_config(args.serve_arch or args.arch)
    if args.reduced:
        cfg_t, cfg_s = cfg_t.reduced(), cfg_s.reduced()

    impl_t = args.attn_impl
    if impl_t == "auto":
        impl_t = auto_attn_impl(args.seq)
    rungs = default_rung_ladder(batch=args.batch, microbatch=args.microbatch,
                                attn_impl=impl_t)
    opt = sgd() if args.optimizer == "sgd" else adam()
    train = TrainSession(
        cfg_t, rungs, optimizer=opt, lr=args.lr,
        compressor=Compressor("none"),
        batch_fn=make_batch_fn(cfg_t, args.batch, args.seq),
        adaptive=True, upgrade_patience=args.upgrade_patience,
        log_every=args.log_every, verbose=False,  # the runtime narrates
        name="train", priority=args.train_priority)
    train.bind(args.ticks)

    max_seq = args.max_seq or 2 * (args.prompt_len + args.gen)
    impl_s = auto_decode_impl(max_seq)
    model = build_model(cfg_s, impl=impl_s)
    params = model.init(jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(model, params, max_batch=args.slots,
                                      max_seq=max_seq,
                                      kv_layout=args.kv_layout,
                                      admission_policy=args.admission_policy)
    rng = np.random.default_rng(args.seed)
    n_req = args.requests or 3 * args.slots
    reqs = _synthetic_requests(rng, n_req, args.prompt_len, args.gen,
                               cfg_s.vocab_size)
    serve = ServeJob(engine, reqs, rungs=default_serve_ladder(args.slots),
                     name="serve", priority=args.serve_priority,
                     upgrade_patience=args.upgrade_patience,
                     slo_p99_s=args.slo_p99 or None)
    jobs = [train, serve]
    if args.fg_burst:
        bursts = []
        for part in args.fg_burst.split(","):
            a, b = part.split(":")
            bursts.append((int(a), int(b)))
        jobs.append(ForegroundAppJob(bursts=bursts))
    return jobs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--serve-arch", default=None,
                    help="serving model (default: same as --arch)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ticks", type=int, default=40,
                    help="runtime quanta (one train step + one decode step "
                         "each); the loop also ends when every job is done")
    # training job
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "naive", "chunked", "pallas"])
    # serving job
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="requests in the stream (default: 3x slots)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=0)
    ap.add_argument("--kv-layout", default="contig",
                    choices=("contig", "paged"),
                    help="serving KV layout; 'paged' exercises the block "
                         "pool (prefix sharing, COW) and publishes pool_* "
                         "telemetry metrics")
    ap.add_argument("--slo-p99", type=float, default=0.0,
                    help="p99 per-token latency SLO in seconds (0 = none); "
                         "the arbiter sheds co-tenants while it is violated "
                         "and holds upgrades until it recovers")
    ap.add_argument("--admission-policy", default="serialize",
                    choices=["serialize", "shed"],
                    help="under KV-pool pressure: 'serialize' stalls the "
                         "queue behind the head, 'shed' rejects with a "
                         "retry-after hint (bounded queue)")
    # shared SoC
    ap.add_argument("--thermal-trace", default="0.2:0.25:3.0",
                    help="shared closed-loop thermal model "
                         "('heat:cool:slowdown[:trigger:release]'; die "
                         "temperature integrates the SUMMED job power draw); "
                         "'' disables")
    ap.add_argument("--interference-trace", default=None,
                    help="scripted co-tenant bursts instead of the thermal "
                         "model ('start:stop:slowdown,...')")
    ap.add_argument("--train-priority", type=float, default=1.0)
    ap.add_argument("--serve-priority", type=float, default=1.0,
                    help="higher priority = arbiter prefers downgrading the "
                         "other job first")
    ap.add_argument("--upgrade-patience", type=int, default=5)
    ap.add_argument("--battery-level", type=float, default=1.0,
                    help="battery fraction; with --battery-j this gates the "
                         "EnergyLoan (depleted budget forces low-power rungs)")
    ap.add_argument("--battery-j", type=float, default=0.0,
                    help="battery capacity in joules (0 disables the energy "
                         "budget); each tick borrows summed-power joules")
    ap.add_argument("--charging-trace", default=None,
                    help="charger plug schedule 'start:stop:watts,...'; "
                         "repays the energy loan while plugged so upgrades "
                         "come back")
    ap.add_argument("--day-ticks", type=int, default=0,
                    help="ticks per 'day'; at each boundary the energy loan "
                         "repays the daily charge surplus (0 disables)")
    ap.add_argument("--fg-burst", default=None,
                    help="foreground-app bursts 'start:stop,...'; while one "
                         "is active every preemptible job is paused "
                         "(training checkpoints + releases its state)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded chaos fault schedule (device "
                         "loss, pool pressure, torn checkpoints, spikes, "
                         "fg bursts) over the run")
    ap.add_argument("--timeline-out", default=None,
                    help="write the merged job-tagged timeline JSON here")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--telemetry-out", default=None,
                    help="directory for the repro.obs telemetry bundle: "
                         "per-tick metrics.jsonl, spans.jsonl, Perfetto "
                         "trace.json, arbiter audit.json")
    ap.add_argument("--log-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quiet", dest="verbose", action="store_false")
    args = ap.parse_args(argv)

    enable_compile_cache()
    # enable telemetry before any job/engine is constructed so every span
    # source (engine, checkpoint manager, runtime) sees the live instance
    tel = obs.enable() if args.telemetry_out else None

    if args.interference_trace and args.thermal_trace:
        args.thermal_trace = ""  # explicit bursts replace the thermal model
    trace = None
    if args.interference_trace:
        trace = InterferenceTrace.parse(args.interference_trace)
    elif args.thermal_trace:
        trace = ThermalTrace.parse(args.thermal_trace)

    energy = None
    if args.battery_j > 0:
        energy = EnergyLoan(battery_j=args.battery_j, daily_charge_j=0.0,
                            daily_usage_j=0.0)
    charging = ChargingTrace.parse(args.charging_trace) \
        if args.charging_trace else None
    chaos = ChaosInjector.random(args.chaos_seed, args.ticks) \
        if args.chaos_seed is not None else None

    jobs = build_jobs(args)
    train, serve = jobs[0], jobs[1]
    rt = SwanRuntime(jobs, trace=trace, energy=energy,
                     battery_level=args.battery_level, charging=charging,
                     day_ticks=args.day_ticks or None, chaos=chaos,
                     verbose=args.verbose)
    res = rt.run(args.ticks)

    s = res.timeline.summary()
    print(f"[swan] {res.ticks} ticks, migrations: {s['n_migrations']} "
          f"(down {s['downgrades']}, up {s['upgrades']})")
    for name, job in res.jobs.items():
        migs = [m for m in res.timeline.migrations if m.job == name]
        print(f"[swan]   {name}: rung={job.active_rung.name} "
              f"work={res.work[name]:.0f} migrations={len(migs)}")
    tl = train.result()
    print(f"[swan] train: final loss {tl.losses[-1]:.4f} "
          f"(first {tl.losses[0]:.4f})" if tl.losses else "[swan] train: idle")
    done = serve.result()
    print(f"[swan] serve: {len(done)} finished, "
          f"{serve.engine.tokens_out} tokens, "
          f"occupancy {serve.engine.occupancy:.2f}")
    if serve.slo_p99_s is not None:
        print(f"[swan] serve SLO: {serve.slo_stats()}")
    if serve.engine.rejected:
        print(f"[swan] serve rejected: {len(serve.engine.rejected)} "
              f"(shed {serve.engine.shed_count}, "
              f"timeout {serve.engine.timeout_count})")
    if res.preemptions:
        print(f"[swan] foreground preemptions: {res.preemptions}")
    if chaos is not None:
        print(f"[swan] chaos: applied {sorted(chaos.applied)}; "
              f"{len(chaos.log)} log entries")
    if args.timeline_out:
        res.timeline.save(args.timeline_out)
        print(f"[swan] merged timeline -> {args.timeline_out}")
    if args.json_out:
        payload = obs.versioned({
            "summary": s, "work": res.work,
            "virtual_time_s": round(res.virtual_time_s, 6),
            "preemptions": res.preemptions,
            "per_job": {n: res.timeline.for_job(n).summary()
                        for n in res.timeline.jobs()}})
        if serve.slo_p99_s is not None:
            payload["slo"] = serve.slo_stats()
        payload["serve_stats"] = serve.engine.stats()
        if chaos is not None:
            payload["chaos"] = chaos.to_json()
        with open(args.json_out, "w") as f:
            json.dump(obs.encode_record(payload), f, indent=1)
    if tel is not None:
        tel.save(args.telemetry_out)
        print(f"[obs] telemetry bundle -> {args.telemetry_out} "
              f"({len(tel.tracer.spans())} spans, "
              f"{len(tel.audit)} audit records)")
    return res


if __name__ == "__main__":
    main()
