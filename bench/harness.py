"""Runs one cell of ``BENCHMARK.json`` once.

Everything that belongs to one cell is found by name under the benchmark's
root: the configuration file the ``configs`` entry names, the mix
``bench/traffic/<traffic>.json`` (whose ``driver`` names
``bench/drivers/<driver>.py``), the limits ``bench/limits/<workload>.json``
and one reader ``bench/metrics/<metric>.py`` per per-layer metric. Drivers
and readers are looked up under the root first and then beside this file,
so a cell made only of data files reuses the drivers and readers here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
from typing import List, Optional

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent


class NoChip(SystemExit):
    pass


class Compiles:
    """Counts programs compiled or loaded from the compile cache while
    open."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self._open and event in self.EVENTS:
            self.count += 1

    def open(self):
        self._open = True

    def close(self):
        self._open = False


@dataclasses.dataclass
class Ctx:
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    tracer: object
    limits: dict
    device: object
    spec: object
    compiles: Compiles
    notes: List[str]
    control: bool = False  # also read the control (bench/control.py only)
    check: bool = True     # compare with the reference (off in bench/sweep.py)

    def note(self, line: str) -> None:
        self.notes.append(line)


@dataclasses.dataclass
class RunView:
    """What a per-layer reader sees."""
    cfg: dict
    mix: dict
    spec: object
    record: dict
    trace: object      # bench.trace.TraceSummary, or None
    peaks: dict


def _load(path: pathlib.Path, label: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + label.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(root: pathlib.Path, kind: str, name: str):
    for base in (root / "bench", HERE):
        p = base / kind / f"{name}.py"
        if p.is_file():
            return _load(p, f"{kind}_{name}")
    raise FileNotFoundError(f"no {kind} named {name!r} under {root} or {HERE}")


def load_cell(root: pathlib.Path, workload: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits" / f"{workload}.json").read_text())
    return bench, cell, cfg, mix, limits["limits"]


def reported(entries: list, workload: str) -> list:
    return [e for e in entries if "workloads" not in e or workload in e["workloads"]]


def layer_metrics(bench: dict, workload: str) -> list:
    """Per-layer metrics of a cell: those that list it, and those without a
    list whose end-to-end metric the cell reports."""
    e2e = {e["name"] for e in reported(bench["end_to_end"], workload)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True, control: bool = False,
             mix_override: Optional[dict] = None, check: bool = True):
    """Returns (result dict for the last line, lines for standard error).
    ``control`` also reads the control's numbers into ``result["control"]``
    (``bench/control.py``); ``mix_override`` and ``check=False`` serve
    ``bench/sweep.py``. The benchmark's own runs use none of them."""
    import jax

    from bench import counts
    from bench.trace import Tracer

    bench, cell, cfg, mix, limits = load_cell(root, workload)
    mix = dict(mix, **(mix_override or {}))
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        raise NoChip(f"bench: {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform!r} device(s)")
    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = find(root, "drivers", mix["driver"])
    spec = counts.Spec.from_config(cfg)
    ctx = Ctx(cfg=cfg, mix=mix, seed=seed, seconds=seconds,
              tracer=Tracer() if trace else None, limits=limits,
              device=devices[0], spec=spec, compiles=Compiles(), notes=[],
              control=control, check=check)
    out = driver.run(ctx)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed}
    if trace:
        summary = ctx.tracer.summary()
        view = RunView(cfg=cfg, mix=mix, spec=spec, record=out.record,
                       trace=summary, peaks=counts.load_peaks(devices[0].device_kind))
        metrics = {}
        for m in layer_metrics(bench, workload):
            value = find(root, "metrics", m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    else:
        metrics = {}
        for m in reported(bench["end_to_end"], workload):
            if m["name"] not in out.e2e:
                raise KeyError(f"driver {mix['driver']!r} gives no {m['name']!r}")
            metrics[m["name"]] = {"value": float(out.e2e[m["name"]]), "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    if control:
        result["control"] = out.record.get("control")
    if not check:
        result["record"] = {k: v for k, v in out.record.items() if k != "work"}
    result["compiles_in_window"] = ctx.compiles.count
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    lines = ctx.notes + [f"problem: {p}" for p in out.problems]
    lines.append(f"compiles in window: {ctx.compiles.count}")
    lines += [f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}" for c in out.checks]
    return _finite(result), lines


def _finite(obj):
    """JSON has no infinity: a missing measurement becomes null."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    return obj
