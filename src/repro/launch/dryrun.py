import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, record memory/cost analysis and roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out reports/dryrun.json
  ... --multi-pod           # 2x16x16 (pod,data,model) instead of 16x16
  ... --mb 8 --remat full   # override the cell's execution-choice defaults
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs import ASSIGNED, SHAPES, applicable, get_config
from repro.core.choices import MeshChoice
from repro.core.profiler import roofline_from_compiled
from repro.engine.rungs import Rung
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (batch_shardings, batch_specs, cache_shardings,
                                decode_specs, param_shardings, replicated)
from repro.launch.steps import build_decode_step, build_prefill_step
from repro.models.registry import build_model
from repro.models.sharding import axis_rules
from repro.optim.optimizers import sgd
from repro.optim.compression import Compressor

# Per-arch execution-choice defaults for the BASELINE dry-run. Microbatch is
# sized so live activations fit v5e HBM with remat=full; the hillclimb
# (EXPERIMENTS.md §Perf) moves these knobs.
TRAIN_MB = {
    "whisper-small": 8, "zamba2-2.7b": 8, "llama3.2-1b": 2, "granite-3-2b": 8,
    "command-r-35b": 16, "nemotron-4-15b": 8, "llama-3.2-vision-11b": 16,
    "deepseek-moe-16b": 2, "deepseek-v3-671b": 16, "rwkv6-7b": 8,
}


def default_choice(arch: str, shape_name: str, multi_pod: bool) -> MeshChoice:
    mesh_shape = (2, 16, 16) if multi_pod else (16, 16)
    axis_names = ("pod", "data", "model") if multi_pod else ("data", "model")
    wide = False  # wide-EP measured worse than narrow (EXPERIMENTS §Perf); hillclimb knob
    if shape_name == "train_4k":
        # per-microbatch batch must stay divisible by the DP extent
        dp_total = 32 if multi_pod else 16
        mb = max(1, min(TRAIN_MB[arch], 256 // dp_total))
        if TRAIN_MB[arch] > mb:
            mb = 256 // dp_total
        return MeshChoice(mesh_shape, axis_names, microbatch=mb,
                          remat="full", chunk=1024, wide_ep=wide)
    return MeshChoice(mesh_shape, axis_names, microbatch=1, remat="none",
                      chunk=2048, wide_ep=wide)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               choice: Optional[MeshChoice] = None, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    choice = choice or default_choice(arch, shape_name, multi_pod)
    # the dry-run lowers exactly what the live engine would execute: the
    # Rung is the executable face of the MeshChoice (engine/rungs.py)
    rung = Rung.from_mesh_choice(choice, param_dtype="bfloat16")
    rec["choice"] = choice.name
    rec["rung"] = rung.signature()
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = choice.rules()
    model = build_model(cfg, impl=rung.attn_impl, chunk=rung.chunk,
                        remat=rung.remat, param_dtype=rung.dtype,
                        moe_cf=choice.moe_cf)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    # set_mesh (not `with mesh:`) installs the abstract mesh that
    # with_sharding_constraint/shard_map resolve during tracing
    with jax.set_mesh(mesh):
        with axis_rules(rules):
            p_shard = param_shardings(params_sds, mesh, rules)
            if shape.mode == "train":
                opt = sgd()
                comp = Compressor(rung.compression)
                step = rung.train_step_fn(model, opt, compressor=comp)
                state_sds = {"params": params_sds, "opt": (), "err": (),
                             "step": jax.ShapeDtypeStruct((), jnp.int32)}
                state_shard = {"params": p_shard, "opt": (), "err": (),
                               "step": replicated(mesh)}
                batch_sds = batch_specs(cfg, shape)
                b_shard = batch_shardings(batch_sds, mesh, rules)
                metrics_shard = {"loss": replicated(mesh), "grad_norm": replicated(mesh)}
                lowered = jax.jit(step, in_shardings=(state_shard, b_shard),
                                  out_shardings=(state_shard, metrics_shard),
                                  donate_argnums=(0,)).lower(state_sds, batch_sds)
            elif shape.mode == "prefill":
                fn = build_prefill_step(model)
                batch_sds = batch_specs(cfg, shape)
                b_shard = batch_shardings(batch_sds, mesh, rules)
                cache_sds = jax.eval_shape(
                    lambda p, b: model.prefill(p, b)[1], params_sds, batch_sds)
                c_shard = cache_shardings(cache_sds, mesh, rules)
                logits_shard = batch_shardings(
                    {"x": jax.ShapeDtypeStruct(
                        (shape.global_batch, 1, cfg.vocab_size),
                        jnp.float32)}, mesh, rules)["x"]
                lowered = jax.jit(fn, in_shardings=(p_shard, b_shard),
                                  out_shardings=(logits_shard, c_shard)
                                  ).lower(params_sds, batch_sds)
            else:  # decode
                # raw step: the AOT jit below owns shardings + donation
                fn = build_decode_step(model, jit=False)
                inputs, cache_sds = decode_specs(model, cfg, shape)
                c_shard = cache_shardings(cache_sds, mesh, rules)
                tok_shard = batch_shardings({"t": inputs["tokens"]}, mesh, rules)["t"]
                logits_shard = batch_shardings(
                    {"x": jax.ShapeDtypeStruct(
                        (shape.global_batch, 1, cfg.vocab_size), jnp.float32)},
                    mesh, rules)["x"]
                lowered = jax.jit(
                    fn, in_shardings=(p_shard, c_shard, tok_shard, replicated(mesh)),
                    out_shardings=(tok_shard, logits_shard, c_shard),
                    donate_argnums=(1,),
                ).lower(params_sds, cache_sds, inputs["tokens"], inputs["cache_len"])

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            terms = roofline_from_compiled(compiled, hlo, choice.n_chips)

    n_tokens = shape.global_batch * (shape.seq_len if shape.mode == "train" else 1)
    model_flops_factor = 6 if shape.mode == "train" else 2
    n_active = cfg.active_param_count()
    model_flops = model_flops_factor * n_active * n_tokens
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        per_device_bytes=terms.per_device_memory,
        per_device_gb=round(terms.per_device_memory / 2 ** 30, 3),
        arg_gb=round(mem.argument_size_in_bytes / 2 ** 30, 3),
        temp_gb=round(mem.temp_size_in_bytes / 2 ** 30, 3),
        fits_hbm=bool(terms.per_device_memory <= 16 * 2 ** 30),
        hlo_flops_global=terms.flops,
        hlo_bytes_global=terms.bytes_accessed,
        collective_bytes_global=terms.collective_bytes,
        compute_s=terms.compute_s, memory_s=terms.memory_s,
        collective_s=terms.collective_s,
        dominant=terms.dominant, latency_s=terms.latency_s,
        model_flops=model_flops,
        useful_flops_ratio=round(model_flops / max(terms.flops, 1), 4),
        roofline_fraction=round(
            (model_flops / (choice.n_chips * 197e12)) / max(terms.latency_s, 1e-12), 4),
        collectives=_collective_summary(hlo),
    )
    if verbose:
        print(json.dumps(rec, indent=None, default=str))
    return rec


def _collective_summary(hlo: str) -> dict:
    from repro.core.profiler import parse_collective_bytes
    return parse_collective_bytes(hlo)


def _merge_out(path, reports):
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            try:
                existing = json.load(f)
            except Exception:
                existing = []
    key = lambda r: (r["arch"], r["shape"], r["mesh"])
    merged = {key(r): r for r in existing}
    for r in reports:
        merged[key(r)] = r
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(list(merged.values()), f, indent=1, default=str)
    os.replace(tmp, path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mb", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--compression", default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--attn-impl", default=None, choices=("chunked", "pallas"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    archs = list(ASSIGNED) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    reports = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                choice = default_choice(arch, shape, mp)
                over = {}
                if args.mb is not None:
                    over["microbatch"] = args.mb
                if args.remat is not None:
                    over["remat"] = args.remat
                if args.compression is not None:
                    over["compression"] = args.compression
                if args.chunk is not None:
                    over["chunk"] = args.chunk
                if args.attn_impl is not None:
                    over["attn_impl"] = args.attn_impl
                if over:
                    choice = dataclasses.replace(choice, **over)
                try:
                    reports.append(lower_cell(arch, shape, multi_pod=mp, choice=choice))
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    reports.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "status": "FAILED", "error": f"{type(e).__name__}: {e}"})
                if args.out:
                    _merge_out(args.out, reports)  # crash-safe incremental write
    n_fail = sum(1 for r in reports if r.get("status") == "FAILED")
    print(f"cells: {len(reports)}, failed: {n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
