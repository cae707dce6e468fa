"""A benchmark root made of tiny cells in a temporary directory: the same
drivers and readers at sizes a CPU test can run."""
from __future__ import annotations

import copy
import json
import pathlib

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
              "vocab_size": 256}
TINY_LOGIT_GAP = 1e-3


def _config(real: str, name: str, **engine) -> dict:
    cfg = json.loads((REPO / "bench" / "configs" / f"{real}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["name"] = name
    cfg["model"].update(TINY_MODEL)
    cfg["model"].pop("attention_multiplier", None)
    if "engine" in cfg:
        cfg["engine"].update(engine)
    return cfg


def _mix(real: str, **over) -> dict:
    mix = json.loads((REPO / "bench" / "traffic" / f"{real}.json").read_text())
    mix.update(over)
    return mix


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """Writes BENCHMARK.json and the files of four tiny cells under ``tmp``:
    the training cell as the benchmark has it, and serving cells for the
    serving driver, whose cells are not in the benchmark yet. Per-layer
    metrics and drivers come from the repository's own files."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    cfgs = {
        "tiny-train": _config("granite-3-2b-train", "tiny-train"),
        "tiny-serve": _config("granite-3-2b-serve", "tiny-serve", max_batch=4,
                              num_blocks=96),
        "tiny-nemo": _config("nemotron-4-15b-stage", "tiny-nemo", max_batch=4,
                             num_blocks=160),
    }
    mixes = {
        "tiny-train": _mix("train-2k", batch=2, seq=64, trace_seconds=0.5),
        "tiny-chat": _mix("chat", rate_per_s=4.0, trace_seconds=0.5,
                          check_requests=3, drain_s=120,
                          prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.5,
                                      "min": 8, "max": 48},
                          output_len={"dist": "lognormal", "median": 4, "sigma": 0.5,
                                      "min": 2, "max": 8}),
        "tiny-docqa": _mix("docqa", session_rate_per_s=1.0, trace_seconds=0.5,
                           check_requests=3, drain_s=120,
                           doc_len={"dist": "uniform", "min": 40, "max": 60},
                           question_len={"dist": "uniform", "min": 4, "max": 8},
                           output_len={"dist": "uniform", "min": 6, "max": 10},
                           think_s={"dist": "exponential", "mean": 0.3}),
        "tiny-backlog": _mix("reason-backlog", requests=12, trace_seconds=0.5,
                             check_requests=2,
                             prompt_len={"dist": "uniform", "min": 4, "max": 12},
                             output_len={"dist": "uniform", "min": 3, "max": 10}),
    }
    cells = [("tiny-train-cell", "tiny-train", "tiny-train", "granite-train-2k"),
             ("tiny-chat-cell", "tiny-serve", "tiny-chat", None),
             ("tiny-docqa-cell", "tiny-nemo", "tiny-docqa", None),
             ("tiny-offline-cell", "tiny-serve", "tiny-backlog", None)]
    as_real = {c[0]: c[3] for c in cells}
    bench = {k: real[k] for k in ("command", "paths", "run_seconds")}
    bench["configs"] = [{"name": n, "source": "test", "file": f"bench/configs/{n}.json",
                         "reduced": [], "why": "test"} for n in cfgs]
    bench["workloads"] = [{"name": c, "config": cf, "traffic": t, "chips": 1, "why": "test"}
                          for c, cf, t, _ in cells]

    def relist(entry):
        e = dict(entry)
        if "workloads" in e:
            e["workloads"] = [c for c, r in as_real.items() if r in entry["workloads"]]
        return e

    # no serving cell is in the benchmark yet: the serving tiny cells'
    # metrics are declared here for them alone
    serving = ["tiny-chat-cell", "tiny-docqa-cell"]
    bench["end_to_end"] = [relist(e) for e in real["end_to_end"]] + [
        {"name": "ttft_p75_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": serving},
        {"name": "itl_p99_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": serving},
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny-offline-cell"]}]
    bench["per_layer"] = [relist(e) for e in real["per_layer"]]
    for sub in ("configs", "traffic", "limits"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for n, c in cfgs.items():
        (tmp / "bench" / "configs" / f"{n}.json").write_text(json.dumps(c))
    for n, m in mixes.items():
        (tmp / "bench" / "traffic" / f"{n}.json").write_text(json.dumps(m))
    for c, r in as_real.items():
        if r is not None:
            lim = json.loads((REPO / "bench" / "limits" / f"{r}.json").read_text())
        else:
            # no serving cell has limits yet; on the CPU the float32 program
            # equals the float32 reference to rounding (it reads 0)
            lim = {"limits": {"logit_gap": TINY_LOGIT_GAP}}
        (tmp / "bench" / "limits" / f"{c}.json").write_text(json.dumps(lim))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
