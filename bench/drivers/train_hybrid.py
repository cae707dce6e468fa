"""Training driver of the layer-pattern hybrid (granite-4.0-h): one
``TrainSession`` with Adam, the Pallas flash kernels in its attention
layers and the chunked SSD scan in its Mamba2 layers, stepped by a
``SwanRuntime`` one tick at a time as ``bench/drivers/train.py`` steps the
dense decoder.

Set-up builds the session around the benchmark's weights, runs its first
three steps (compiling the step with telemetry on, so the model's
``hybrid_layers`` and ``ssd_chunks`` gauges are set as it traces, and
copied into the record) and reads what the check needs from their state.
The window then keeps stepping the same session on fresh batches until
``seconds`` have passed; every step ends in the session's blocking read of
its loss. After the window the session is freed and the plain reference
(``bench/hybrid/reference.py``) repeats the first three steps for the
check: ``grad_gap`` and ``update_gap`` as the dense driver takes them.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.drivers.common import PROGRAM_EPS, Check, Outcome, peak_bytes
from bench.drivers.train import CHECKED_STEPS, _gaps, _norms
from bench.hybrid import Dims, reference, weights
from bench.reference import delta_norms

# configuration-file key -> the program's ModelConfig field
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "shared_intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "hidden_act": "activation", "normalization_function": "norm",
    "tie_word_embeddings": "tie_embeddings", "mamba_d_state": "ssm_state",
    "mamba_expand": "ssm_expand", "mamba_d_conv": "ssm_conv_width",
    "mamba_n_groups": "ssm_n_groups", "mamba_conv_bias": "ssm_conv_bias",
    "embedding_multiplier": "embedding_multiplier",
    "attention_multiplier": "attention_multiplier",
    "residual_multiplier": "residual_multiplier", "logits_scaling": "logits_scaling",
}
POSITIONS = {"nope": "none", "rope": "rope"}  # position_embedding_type -> pos_embedding


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file's top level: the
    registry architecture with the file's sizes. Raises where the file
    states something the program cannot run."""
    from repro.configs import get_config
    from repro.models.ssm import MAMBA_HEAD_DIM, SSD_CHUNK
    over = {f: cfg[k] for k, f in PROGRAM_FIELDS.items()}
    over["layer_types"] = tuple(cfg["layer_types"])
    over["head_dim"] = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    over["pos_embedding"] = POSITIONS[cfg["position_embedding_type"]]
    pc = dataclasses.replace(get_config(cfg["arch"]), **over)
    stated = {"rms_norm_eps": (cfg["rms_norm_eps"], PROGRAM_EPS),
              "intermediate_size": (cfg["intermediate_size"], pc.d_ff),
              "mamba_d_head": (cfg["mamba_d_head"], MAMBA_HEAD_DIM),
              "mamba_chunk_size": (cfg["mamba_chunk_size"], SSD_CHUNK),
              "mamba_n_heads": (cfg["mamba_n_heads"],
                                pc.ssm_expand * pc.d_model // MAMBA_HEAD_DIM),
              "attention_bias": (cfg["attention_bias"], False),
              "mamba_proj_bias": (cfg["mamba_proj_bias"], False),
              "num_local_experts": (cfg["num_local_experts"], 0)}
    for k, (file, prog) in stated.items():
        if file != prog:
            raise ValueError(f"the program runs {k}={prog!r}, the file states {file!r}")
    return pc


MAMBA = {"w_in": "in_proj", "w_conv": "conv.weight", "b_conv": "conv.bias",
         "A_log": "A_log", "dt_bias": "dt_bias", "D": "D",
         "norm_scale": "gate_norm.scale", "w_out": "out_proj"}
MLP = ("w_gate", "w_up", "w_down")
ATTN = ("wq", "wk", "wv", "wo")


def program_params(w: dict, m: Dims, model) -> dict:
    """The program's parameter tree holding the benchmark's arrays, run by
    run (no copies); checked leaf by leaf against the tree the program
    itself would build."""
    runs = []
    for i, (kind, _) in enumerate(m.runs()):
        r = f"runs.{i}."
        lp = {"ln1": {"scale": w[r + "in_norm.scale"]},
              "ln2": {"scale": w[r + "mlp_norm.scale"]},
              "mlp": {k: w[r + k] for k in MLP}}
        if kind == "mamba":
            lp["mamba"] = {k: w[r + v] for k, v in MAMBA.items()}
        else:
            lp["attn"] = {k: w[r + k] for k in ATTN}
        runs.append(lp)
    p = {"embed": w["embed"], "ln_f": {"scale": w["final_norm.scale"]}, "runs": runs}
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got_s = jax.tree_util.tree_structure(p)
    want_s = jax.tree_util.tree_structure(want)
    if got_s != want_s:
        raise ValueError(f"parameter tree {got_s} is not the program's {want_s}")
    for a, b in zip(jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} != program's {b.shape} {b.dtype}")
    return p


def canonical(p: dict) -> dict:
    """The inverse of :func:`program_params`: benchmark names for the
    program's leaves."""
    w = {"embed": p["embed"], "final_norm.scale": p["ln_f"]["scale"]}
    for i, lp in enumerate(p["runs"]):
        r = f"runs.{i}."
        w[r + "in_norm.scale"] = lp["ln1"]["scale"]
        w[r + "mlp_norm.scale"] = lp["ln2"]["scale"]
        w.update({r + k: lp["mlp"][k] for k in MLP})
        if "mamba" in lp:
            w.update({r + v: lp["mamba"][k] for k, v in MAMBA.items()})
        else:
            w.update({r + k: lp["attn"][k] for k in ATTN})
    return w


def _gauges(tel) -> dict:
    """The model's gauges as set when it traced: ``hybrid_layers`` by
    kind and ``ssd_chunks`` by chunk length."""
    out = {}
    for name, label in (("hybrid_layers", "kind"), ("ssd_chunks", "chunk")):
        metric = tel.metrics.get(name)
        out[name] = {} if metric is None else {
            row["labels"][label]: int(row["value"]) for row in metric.series()}
    return out


def _reference(cfg, m, seed, batches, dtype=jnp.float32, precision="highest"):
    """The plain reference's losses, first-gradient norms and change norms
    over ``batches`` from the seed's weights."""
    ref = reference.train(cfg, weights.make(m, seed), batches,
                          dtype=dtype, precision=precision)
    ref["delta_norms"] = delta_norms(ref.pop("weights"), weights.make(m, seed))
    return ref


def run(ctx) -> Outcome:
    from repro import obs
    from repro.engine.rungs import Rung
    from repro.engine.runtime import SwanRuntime
    from repro.engine.session import TrainSession
    from repro.models.registry import build_model
    from repro.optim.optimizers import adam

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    o = cfg["optimizer"]
    t0 = time.perf_counter()
    pcfg = program_config(cfg)
    m = Dims.from_config(cfg)
    opt = adam(b1=o["b1"], b2=o["b2"], eps=o["eps"])
    rung = Rung(name="bench", attn_impl=cfg["attention"], remat=cfg["remat"])
    params = program_params(weights.make(m, seed), m, build_model(pcfg))
    state = {"params": params, "opt": opt.init(params), "err": (),
             "step": jnp.zeros((), jnp.int32)}
    del params
    session = TrainSession(
        pcfg, [rung], optimizer=opt, lr=o["lr"], adaptive=False, verbose=False,
        batch_fn=lambda step: traffic.train_batch(mix, m.vocab, seed, step))
    session.bind(1 << 40, state=state, rng_seed=0)
    del state
    runtime = SwanRuntime([session])
    tick = 0

    def step():
        nonlocal tick
        with jax.profiler.TraceAnnotation("bench.train_step"):
            runtime.run(tick + 1, start=tick)
        tick += 1

    tel = obs.Telemetry(enabled=True)
    quiet = obs.set_telemetry(tel)
    try:
        step()  # traces and compiles the step
    finally:
        obs.set_telemetry(quiet)
    gauges = _gauges(tel)
    b1 = o["b1"]
    grad_prog = {k: v / (1 - b1) for k, v in
                 _norms(canonical(session.result().state["opt"]["m"])).items()}
    for _ in range(CHECKED_STEPS - 1):
        step()
    delta_prog = delta_norms(canonical(session.result().state["params"]),
                             weights.make(m, seed))
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
        if tracer is not None and not traced and n == mix.get("trace_after_steps", 2):
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
    batches = [traffic.train_batch(mix, m.vocab, seed, i) for i in range(CHECKED_STEPS)]
    ref = _reference(cfg, m, seed, batches)
    med = float(np.median(list(ref["grad_norms"].values())))
    moved = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * med}
    prog = {"losses": loss_prog, "grad_norms": grad_prog, "delta_norms": delta_prog}
    gaps = _gaps(prog, ref, moved)
    checks = [Check(k, v, ctx.limits[k]) for k, v in gaps.items() if k in ctx.limits]
    control = None
    if ctx.control:
        # the reference in bfloat16 in the program's place
        low = _reference(cfg, m, seed, batches, jnp.bfloat16, "default")
        control = {"control": _gaps(low, ref, moved)}
    tokens = mix["batch"] * mix["seq"]
    ctx.note(f"train_hybrid: {n} steps in {window_s:.4f} s window; losses program "
             f"{loss_prog} reference {ref['losses']} (loss_gap {gaps['loss_gap']!r}, "
             f"not compared); leaves compared for the update {len(moved)} of "
             f"{len(ref['grad_norms'])}; gauges {gauges}")
    return Outcome(
        e2e={"train_tokens_per_s": n * tokens / window_s, "setup_s": setup_s},
        attempted=n + CHECKED_STEPS, failed=len(bad), checks=checks,
        memory_peak_bytes=mem,
        record={"steps": n, "window_s": window_s, "tokens_per_step": tokens,
                "batch": mix["batch"], "seq": mix["seq"], "control": control,
                **gauges},
        problems=[f"{len(bad)} non-finite losses"] if bad else [])
