"""Device trace of a window: start and stop JAX's profiler, and reduce the
trace to the numbers the per-layer readers use.

The reduction reads only what the profiler records on the device planes:
the ``XLA Ops`` line (every operation the chip ran, each with its
``hlo_module``) and the ``XLA Modules`` line (every program launch). Host
annotations written by the drivers (``bench.*``, through
``jax.profiler.TraceAnnotation``) name what the host was doing in each idle
gap. Timestamps of host and device planes share the profiler's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_ANNOTATION = "bench.trace_window"
HOST_PREFIX = "bench."


PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # length of the traced window
    busy_s: float                   # union of device op intervals, mean over chips
    n_chips: int
    module_s: Dict[str, float]      # program name -> device seconds of its launches
    module_calls: Dict[str, int]    # program name -> launches
    op_s: Dict[Tuple[str, str], float]   # (program, op) -> device seconds
    op_calls: Dict[Tuple[str, str], int]
    pallas: frozenset               # the (program, op) keys that are Pallas kernels
    idle_gaps: List[Tuple[str, float]]  # (host activity, seconds), longest first

    def kernel_s(self, program: str) -> Tuple[float, int]:
        """Device seconds and calls of the Pallas kernels run inside the
        programs whose name matches ``program``."""
        rx = re.compile(program)
        keys = [k for k in self.pallas if rx.search(k[0])]
        return (sum(self.op_s[k] for k in keys), sum(self.op_calls[k] for k in keys))

    def program_s(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        s = sum(v for k, v in self.module_s.items() if rx.search(k))
        n = sum(v for k, v in self.module_calls.items() if rx.search(k))
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[f"{m}:{o}" + (" (pallas)" if (m, o) in self.pallas else ""), v]
                               for (m, o), v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def module_name(raw: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", raw.strip())


def op_name(raw: str) -> str:
    """An op event's name is its HLO instruction: keep the instruction's
    own name (``%fusion.6 = f32[...] fusion(...)`` -> ``%fusion.6``)."""
    return raw.split(" = ", 1)[0].strip()


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`.

    The traced window is the host annotation ``bench.trace_window``; device
    time outside it is dropped. An op belongs to the program launch whose
    interval holds its start (one stream per chip); where a trace has no
    program line, to its ``hlo_module`` stat. Pallas kernels are the ops
    whose instruction is a ``tpu_custom_call``. Raises ``ValueError`` when
    the trace holds no device operation or no window annotation.
    """
    host_events: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(line.events)
                elif line.name == "XLA Modules":
                    mods.extend(line.events)
            devices.append((ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend((ev.start_ns, ev.end_ns, ev.name)
                                   for ev in line.events
                                   if ev.name.startswith(HOST_PREFIX))
    windows = [(a, b) for a, b, n in host_events if n == WINDOW_ANNOTATION]
    if not devices or not any(ops for ops, _ in devices):
        raise ValueError("trace holds no device operations")
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_ANNOTATION!r} annotation")
    lo, hi = windows[0]
    window_s = (hi - lo) / 1e9
    module_s: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, int] = defaultdict(int)
    op_s: Dict[Tuple[str, str], float] = defaultdict(float)
    op_calls: Dict[Tuple[str, str], int] = defaultdict(int)
    pallas = set()
    busy_total = 0.0
    gaps: List[Tuple[float, float]] = []
    for ops, mods in devices:
        launches = sorted((ev.start_ns, ev.end_ns, module_name(str(ev.name)))
                          for ev in mods)
        starts = [a for a, _, _ in launches]
        for a, b, name in launches:
            if b <= lo or a >= hi:
                continue
            module_s[name] += (min(b, hi) - max(a, lo)) / 1e9
            if a >= lo:
                module_calls[name] += 1
        iv = []
        for ev in ops:
            a, b = ev.start_ns, ev.end_ns
            if b <= lo or a >= hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            if launches and i >= 0 and a < launches[i][1]:
                mod = launches[i][2]
            else:
                mod = module_name(str(_stats(ev).get("hlo_module", "")))
                if not launches and mod:
                    module_s[mod] += (min(b, hi) - max(a, lo)) / 1e9
            raw = str(ev.name)
            key = (mod, op_name(raw))
            op_s[key] += (min(b, hi) - max(a, lo)) / 1e9
            op_calls[key] += 1
            if PALLAS in raw:
                pallas.add(key)
            iv.append((a, b))
        merged = _clip(_union(iv), lo, hi)
        busy_total += sum(b - a for a, b in merged) / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i])
    n = len(devices)
    labelled = []
    host = [(a, b, name) for a, b, name in host_events
            if name != WINDOW_ANNOTATION]
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (a + b) / 2
        inside = [(hb - ha, name) for ha, hb, name in host if ha <= mid <= hb]
        label = min(inside)[1] if inside else "untraced host work"
        labelled.append((label, (b - a) / 1e9))
    return TraceSummary(window_s=window_s, busy_s=busy_total / n, n_chips=n,
                        module_s=dict(module_s),
                        module_calls=dict(module_calls), op_s=dict(op_s),
                        op_calls=dict(op_calls), pallas=frozenset(pallas),
                        idle_gaps=labelled)


class Tracer:
    """Profiles one stretch of a run into a private directory under
    ``TMPDIR``. ``stop`` only ends the profile; ``summary`` reads and reduces
    it once the window is over, then deletes the raw trace."""

    def __init__(self):
        self._dir: Optional[str] = None
        self._annotation = None
        self.running = False
        self.started = 0.0

    def start(self) -> None:
        import time

        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._annotation.__enter__()
        self.running = True
        self.started = time.perf_counter()

    def stop(self) -> None:
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def summary(self) -> TraceSummary:
        from jax.profiler import ProfileData
        if self._dir is None:
            raise ValueError("nothing was traced")
        try:
            paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise ValueError("the profiler wrote no trace")
            return reduce_profile(ProfileData.from_file(paths[0]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
