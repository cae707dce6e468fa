"""Decode-attention benchmark: naive oracle vs Pallas single-query kernel.

Sweeps the paper-config head geometries across batch x cache-length grids
and times one decode-attention step (cache already updated; per-sequence
ragged lengths) for ``impl`` in {naive, pallas}. Decode is KV-bandwidth
bound, so the figure of merit is tokens/sec at a given cache length — the
quantity the continuous-batching serve loop maximizes.

Every timed cell also carries a correctness gate: the two impls must agree
on the attention output at f32 (atol 2e-5), and a reduced full-model greedy
decode must be token-identical between ``impl="naive"`` and
``impl="pallas"``. The gate is what CI enforces; the timing columns are
best-effort on CPU, where the Pallas kernel runs in interpret mode and the
naive jnp path is the honest baseline (recorded as ``backend``/``interpret``
in the JSON so per-PR trajectories only compare like with like).

Writes ``BENCH_decode.json``; ``--full`` uses serving-scale cache lengths
(>= 512, the regime the ISSUE acceptance targets) and is only meaningful on
a real accelerator.

Usage:
  PYTHONPATH=src python benchmarks/decode_bench.py [--full] [--out BENCH_decode.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

IMPLS = ("naive", "pallas")


def _shapes(full: bool):
    """(arch, B, cache_len, H, K, hd) cells from paper-config head geometry."""
    from repro.configs import get_config
    # always include the >= 512 regime the acceptance gate targets; --full
    # adds the serving-scale tail only meaningful on a real accelerator
    cache_lens = (512, 2048) if full else (128, 512)
    batches = (4,) if full else (2,)
    out = []
    for arch in ("llama3.2-1b", "granite-3-2b", "command-r-35b"):
        cfg = get_config(arch)
        for B in batches:
            for cl in cache_lens:
                out.append((arch, B, cl, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim))
    return out


def _time(fn, *args, iters: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_decode_attention(full: bool, iters: int):
    """Attention-op level: one ragged decode step, naive vs pallas."""
    from repro.kernels import ops as kops
    from repro.models.attention import naive_attention

    results = []
    for arch, B, cache_len, H, K, hd in _shapes(full):
        key = jax.random.PRNGKey(0)
        Smax = cache_len
        q = jax.random.normal(key, (B, 1, H, hd))
        # head-major cache, as the model keeps it; the naive oracle reads
        # the sequence-major view
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, K, Smax, hd))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, K, Smax, hd))
        # ragged: half the slots at full length, half at half length
        lengths = jnp.asarray([Smax if b % 2 == 0 else Smax // 2
                               for b in range(B)], jnp.int32)

        fns = {
            "naive": jax.jit(lambda q, k, v, ln: naive_attention(
                q, k.swapaxes(1, 2), v.swapaxes(1, 2), causal=False,
                kv_len=ln)),
            "pallas": jax.jit(lambda q, k, v, ln: kops.decode_attention(
                q, k, v, ln)),
        }
        outs = {}
        cell = []
        for impl in IMPLS:
            rec = {"bench": "decode_attn", "shape": arch, "impl": impl,
                   "B": B, "cache_len": cache_len, "H": H, "K": K, "hd": hd}
            try:
                outs[impl] = np.asarray(fns[impl](q, k, v, lengths))
                rec["us_per_step"] = round(_time(fns[impl], q, k, v, lengths,
                                                 iters=iters), 1)
                rec["tok_s"] = round(B / (rec["us_per_step"] / 1e6), 1)
                rec["status"] = "ok"
            except Exception as e:  # a broken impl is recorded, not fatal
                rec["status"] = f"error: {type(e).__name__}: {e}"
            cell.append(rec)
        if all(r["status"] == "ok" for r in cell):
            err = float(np.abs(outs["naive"] - outs["pallas"]).max())
            ok = bool(err < 2e-5)
            speedup = cell[0]["us_per_step"] / cell[1]["us_per_step"]
            for r in cell:
                r["parity_max_err"] = err
                r["parity_ok"] = ok
                r["pallas_speedup"] = round(speedup, 3)
        results.extend(cell)
    return results


def bench_model_parity(steps: int = 6):
    """Full-model gate: greedy decode must be token-identical naive vs pallas."""
    from repro.configs import ASSIGNED
    from repro.launch.steps import greedy_decode_tokens
    from repro.models import build_model

    results = []
    for arch in ("llama3.2-1b", "deepseek-v3-671b"):  # GQA and MLA
        cfg = ASSIGNED[arch].reduced()
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (2, 1), 0, cfg.vocab_size)
        rec = {"bench": "decode_parity", "shape": arch, "impl": "pallas",
               "steps": steps}
        try:
            streams = {}
            for impl in IMPLS:
                model = build_model(cfg, impl=impl, moe_cf=100.0)
                params = model.init(key)
                streams[impl] = greedy_decode_tokens(
                    model, params, toks, steps=steps, max_len=steps + 2)
            same = bool((streams["naive"] == streams["pallas"]).all())
            rec["token_identical"] = same
            rec["status"] = "ok" if same else "error: token mismatch"
        except Exception as e:
            rec["status"] = f"error: {type(e).__name__}: {e}"
        results.append(rec)
    return results


def run(fast: bool = True):
    """Harness entry (benchmarks/run.py): yields (name, us, derived) rows.

    Raises after yielding the good rows if any impl errored or a parity
    gate failed, so a broken decode path lands in the harness's failure
    accounting instead of silently shrinking the row count.
    """
    bad = []
    for rec in bench_decode_attention(full=not fast, iters=2 if fast else 5):
        if rec["status"] == "ok":
            yield (f"decode_{rec['shape']}_L{rec['cache_len']}_{rec['impl']}",
                   rec["us_per_step"], f"tok_s={rec['tok_s']}")
            if not rec.get("parity_ok", True):
                bad.append(f"{rec['shape']}/L{rec['cache_len']}: parity "
                           f"err={rec.get('parity_max_err')}")
        else:
            bad.append(f"{rec['shape']}/{rec['impl']}: {rec['status']}")
    for rec in bench_model_parity():
        if rec["status"] != "ok":
            bad.append(f"{rec['shape']}: {rec['status']}")
    if bad:
        raise RuntimeError("decode bench failures: " + "; ".join(sorted(set(bad))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="serving-scale cache lengths (accelerator only)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--out", default="BENCH_decode.json")
    args = ap.parse_args()
    iters = args.iters or (5 if args.full else 2)

    results = bench_decode_attention(args.full, iters) + bench_model_parity()

    print("name,us_per_call,derived")
    for rec in results:
        name = f"{rec['bench']}_{rec['shape']}"
        if rec["bench"] == "decode_attn":
            name += f"_L{rec['cache_len']}_{rec['impl']}"
        if rec["status"] != "ok":
            print(f"{name},0,{rec['status']}")
        elif rec["bench"] == "decode_attn":
            print(f"{name},{rec['us_per_step']},tok_s={rec['tok_s']}")
        else:
            print(f"{name},0,token_identical={rec['token_identical']}")

    # timing gate: pallas must beat naive tokens/sec at cache_len >= 512.
    # Only claimable on a Mosaic backend — in interpret mode the kernel is
    # being emulated and the verdict is recorded as None (gate = parity).
    interpret = jax.default_backend() != "tpu"
    long_cells = [r["pallas_speedup"] for r in results
                  if r.get("cache_len", 0) >= 512 and "pallas_speedup" in r]
    timing_gate = None if (interpret or not long_cells) else \
        bool(min(long_cells) > 1.0)
    payload = {"mode": "full" if args.full else "ci",
               "backend": jax.default_backend(),
               "interpret": interpret,
               "timing_gate_pallas_wins_at_512": timing_gate,
               "iters": iters, "results": results}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {args.out} ({len(results)} records)", file=sys.stderr)
    bad = [r for r in results if r["status"] != "ok"
           or not r.get("parity_ok", True)]
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
