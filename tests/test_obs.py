"""Observability plane (repro.obs): metrics registry, span tracer, arbiter
audit, schema-versioned serialization, and the end-to-end telemetry bundle
from a mixed-workload run.

The contract under test: telemetry off is free (shared no-op handles, no
records; spans are only profiler annotations), telemetry on is complete
(every serve/train/pool/energy/thermal stat in one schema, every phase a
span, every migration an audit record carrying the scores that decided
it)."""
import glob
import io
import json

import numpy as np
import pytest

from repro import obs
from repro.engine.timeline import Timeline
from repro.obs.metrics import NOOP, MetricsRegistry
from repro.obs.schema import SCHEMA_VERSION, encode_record, versioned
from repro.obs.trace import SpanTracer


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    """Fresh disabled global per test; whatever a test installs (including
    CLI mains calling obs.enable()) is torn back down afterwards."""
    prev = obs.set_telemetry(obs.Telemetry(enabled=False))
    yield
    obs.set_telemetry(prev)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_labels_round_trip():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests seen")
    c.labels(job="serve", outcome="ok").inc()
    c.labels(job="serve", outcome="ok").inc(2)
    c.labels(outcome="shed", job="serve").inc()  # label order is irrelevant
    assert c.value(job="serve", outcome="ok") == 3.0
    assert c.value(outcome="ok", job="serve") == 3.0
    assert c.value(job="serve", outcome="shed") == 1.0
    assert c.value(job="serve", outcome="missing") is None

    g = reg.gauge("occupancy")
    g.set(0.25)
    g.set(0.75)  # gauges overwrite
    assert g.value() == 0.75

    snap = reg.snapshot()
    assert snap["schema_version"] == SCHEMA_VERSION
    series = snap["metrics"]["requests_total"]["series"]
    assert {"labels": {"job": "serve", "outcome": "ok"}, "value": 3.0} in series

    line = reg.snapshot_line(7)
    assert line["tick"] == 7
    assert line["metrics"]["requests_total{job=serve,outcome=ok}"] == 3.0
    assert line["metrics"]["occupancy"] == 0.75


def test_metrics_histogram_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("latency_s")
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    s = h.value()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert h.quantile(0.5) == pytest.approx(50.5)
    assert h.quantile(0.0) == 1.0 and h.quantile(1.0) == 100.0
    assert s["p99"] == pytest.approx(99.01)
    # ring buffer: quantiles track the most recent cap samples
    hc = reg.histogram("small", max_samples=4)
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        hc.labels(job="x").observe(v)
    assert hc.value(job="x")["count"] == 5  # count/sum stay exact
    assert hc.value(job="x")["max"] == 100.0
    assert hc.quantile(1.0, job="x") == 100.0  # 1.0 evicted from the ring
    assert hc.quantile(0.0, job="x") == 2.0


def test_metrics_disabled_is_shared_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("a")
    g = reg.gauge("b")
    h = reg.histogram("c")
    assert c is NOOP and g is NOOP and h is NOOP
    assert c.labels(job="x") is NOOP
    # all mutations are free no-ops and nothing is registered
    c.inc()
    g.set(1.0)
    h.observe(2.0)
    assert reg.names() == []
    assert reg.snapshot()["metrics"] == {}


def test_metrics_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError, match="registered as counter"):
        reg.gauge("m")
    with pytest.raises(TypeError, match="is a counter"):
        reg.counter("m").set(1.0)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_chrome_trace_ordering():
    tr = SpanTracer()
    with tr.span("tick", tick=0):
        with tr.span("step", job="train"):
            pass
        with tr.span("decode", batch=2):
            pass
    recs = {s.name: s for s in tr.spans()}
    assert recs["tick"].depth == 0
    assert recs["step"].depth == 1 and recs["decode"].depth == 1
    # children are contained in the parent interval, and ordered
    tick, step, dec = recs["tick"], recs["step"], recs["decode"]
    assert tick.ts_us <= step.ts_us
    assert step.ts_us + step.dur_us <= dec.ts_us
    assert dec.ts_us + dec.dur_us <= tick.ts_us + tick.dur_us

    doc = tr.chrome_trace()
    doc2 = json.loads(json.dumps(doc))  # must be strict-JSON serializable
    xs = [e for e in doc2["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["step", "decode", "tick"]  # exit order
    assert all(e["pid"] == 1 and "ts" in e and "dur" in e for e in xs)
    assert xs[0]["args"] == {"job": "train"}
    metas = [e for e in doc2["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in metas)
    assert any(e["name"] == "thread_name" for e in metas)
    assert doc2["otherData"]["schema_version"] == SCHEMA_VERSION
    assert doc2["otherData"]["dropped_spans"] == 0


def test_tracer_disabled_returns_shared_noop_span():
    # a disabled span is only its profiler annotation: it records nothing
    # in the tracer, nested or not
    tr = SpanTracer(enabled=False)
    with tr.span("a", k=1):
        with tr.span("b"):
            assert tr.active_stack() == {}
    assert tr.spans() == [] and tr.by_name() == {}


def test_tracer_records_exception_and_reraises():
    tr = SpanTracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    (rec,) = tr.spans()
    assert rec.args["error"] == "ValueError"


def test_tracer_cap_counts_drops():
    tr = SpanTracer(max_spans=2)
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 2 and tr.dropped == 2
    assert tr.chrome_trace()["otherData"]["dropped_spans"] == 2


# ---------------------------------------------------------------------------
# schema / serialization
# ---------------------------------------------------------------------------


def test_encode_record_strict_json():
    rec = encode_record({"inf": float("inf"), "ninf": float("-inf"),
                         "nan": float("nan"), "np": np.float32(1.5),
                         "ok": 2.0, "nested": [np.int64(3), float("inf")]})
    assert rec == {"inf": None, "ninf": None, "nan": None, "np": 1.5,
                   "ok": 2.0, "nested": [3, None]}
    json.dumps(rec, allow_nan=False)  # strict JSON, no Infinity/NaN literals
    assert versioned({"a": 1}) == {"schema_version": SCHEMA_VERSION, "a": 1}


def test_timeline_merged_round_trips_bitwise():
    a, b = Timeline(), Timeline()
    a.record_step(step=0, rung="full", latency_s=0.5, observed_s=0.5,
                  loss=2.25, warmup=True, work=8.0)
    a.record_migration(step=1, from_rung="full", to_rung="accum",
                       reason="interference", kind="in-place", cost_s=0.125)
    b.record_step(step=0, rung="serve-full", latency_s=0.25, observed_s=0.25,
                  loss=0.0, work=4.0)
    merged = Timeline.merged({"train": a, "serve": b})
    doc = merged.to_json()
    assert doc["schema_version"] == SCHEMA_VERSION
    wire = json.loads(json.dumps(doc))
    back = Timeline.from_json(wire)  # extra top-level keys must be ignored
    assert back.to_json() == doc
    assert set(back.jobs()) == {"train", "serve"}


def test_audit_log_round_trips_bitwise():
    log = obs.AuditLog()
    log.record(tick=3, job="train", event="commit", direction="down",
               rule="interference", from_rung="full", to_rung="accum",
               scores={"train": 4.5, "serve": float("-inf")},
               slo_headroom={"serve": 0.125, "train": None},
               proposals={"train": "down"},
               energy={"loan_j": 2.0, "available": True,
                       "battery_level": 0.5},
               thermal={"temp": 0.75, "throttled": True})
    log.record(tick=4, job="serve", event="veto", direction="down",
               rule="slo", detail="ladder bottom")
    doc = log.to_json()
    assert doc["schema_version"] == SCHEMA_VERSION
    wire = json.loads(json.dumps(doc, allow_nan=False))  # -inf became None
    back = obs.AuditLog.from_json(wire)
    assert back.to_json() == wire
    assert len(back) == 2
    assert back.commits()[0].scores == {"train": 4.5, "serve": None}
    assert back.for_tick(4)[0].event == "veto"
    assert back.for_job("serve")[0].detail == "ladder bottom"


# ---------------------------------------------------------------------------
# Telemetry bundle + debug dump
# ---------------------------------------------------------------------------


def test_telemetry_bundle_save_and_debug_dump(tmp_path):
    tel = obs.Telemetry(enabled=True)
    tel.metrics.gauge("g").set(1.0)
    with tel.span("outer", tick=0):
        with tel.span("inner"):
            buf = io.StringIO()
            tel.debug_dump(file=buf, last=5)
    dump = buf.getvalue()
    assert "active span stacks" in dump
    assert "outer" in dump and "inner" in dump
    tel.audit.record(tick=0, job="train", event="commit", direction="down",
                     rule="energy", from_rung="full", to_rung="accum")
    tel.snap(0)

    paths = tel.save(str(tmp_path / "tel"))
    lines = [json.loads(l) for l in open(paths["metrics"])]
    assert lines[0] == versioned({"stream": "metrics"})
    assert lines[1]["tick"] == 0 and lines[1]["metrics"]["g"] == 1.0
    assert lines[-1]["tick"] == "final"
    span_lines = [json.loads(l) for l in open(paths["spans"])]
    assert span_lines[0] == versioned({"stream": "spans"})
    assert {s["name"] for s in span_lines[1:]} == {"outer", "inner"}
    trace = json.load(open(paths["trace"]))
    assert {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"} == \
        {"outer", "inner"}
    audit = json.load(open(paths["audit"]))
    assert audit["schema_version"] == SCHEMA_VERSION
    assert audit["records"][0]["rule"] == "energy"

    buf2 = io.StringIO()
    tel.debug_dump(file=buf2, last=5)
    out2 = buf2.getvalue()
    assert "no active spans" in out2
    assert "audit records" in out2 and "latest metrics snapshot" in out2


def test_disabled_telemetry_dump_and_noop_identity():
    tel = obs.get_telemetry()
    assert not tel.enabled
    with tel.span("x", k=1):  # records nothing in the bundle
        pass
    assert tel.tracer.spans() == []
    assert tel.metrics.counter("c") is NOOP
    buf = io.StringIO()
    tel.debug_dump(file=buf)
    assert "telemetry disabled" in buf.getvalue()
    tel.snap(0)
    assert tel.snapshots == []


# ---------------------------------------------------------------------------
# spans as profiler annotations (telemetry disabled)
# ---------------------------------------------------------------------------

TRAIN_PHASES = ("runtime.tick", "train.batch", "train.step", "train.dispatch",
                "train.wait", "runtime.observe", "runtime.arbitrate")


def _tiny_train_runtime(ticks):
    from repro.configs.base import ModelConfig
    from repro.engine.rungs import Rung
    from repro.engine.runtime import SwanRuntime
    from repro.engine.session import TrainSession
    from repro.launch.train import make_batch_fn
    from repro.optim.optimizers import sgd
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      tie_embeddings=True, source="test")
    session = TrainSession(cfg, [Rung(name="r", attn_impl="naive")],
                           optimizer=sgd(), batch_fn=make_batch_fn(cfg, 2, 16),
                           adaptive=False, verbose=False)
    session.bind(ticks)
    return SwanRuntime([session])


def _profile_host_events(logdir):
    """(start_ns, end_ns, name) of every event on the host planes of the
    one profile captured under ``logdir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    return [(ev.start_ns, ev.end_ns, ev.name) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_train_phase_spans_reach_the_profile_with_telemetry_disabled(tmp_path):
    import jax
    rt = _tiny_train_runtime(3)
    rt.run(1)  # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rt.run(3, start=1)
    finally:
        jax.profiler.stop_trace()
    events = _profile_host_events(tmp_path)
    by = {n: sorted(e for e in events if e[2] == n) for n in TRAIN_PHASES}
    assert {n: len(v) for n, v in by.items()} == {n: 2 for n in TRAIN_PHASES}

    def within(inner, outer):
        return outer[0] <= inner[0] and inner[1] <= outer[1]

    def before(a, b):
        return a[1] <= b[0]

    for i in range(2):
        ph = {n: by[n][i] for n in TRAIN_PHASES}
        assert all(within(ph[n], ph["runtime.tick"]) for n in TRAIN_PHASES)
        assert within(ph["train.dispatch"], ph["train.step"])
        assert within(ph["train.wait"], ph["train.step"])
        assert before(ph["train.dispatch"], ph["train.wait"])
        assert before(ph["train.batch"], ph["train.step"])
        assert before(ph["train.step"], ph["runtime.observe"])
        assert before(ph["runtime.observe"], ph["runtime.arbitrate"])
    assert before(by["runtime.tick"][0], by["runtime.tick"][1])
    assert obs.get_telemetry().tracer.spans() == []


def test_disabled_spans_leave_the_bundle_empty_without_a_capture(tmp_path):
    rt = _tiny_train_runtime(2)
    rt.run(2)
    tel = obs.get_telemetry()
    assert tel.tracer.spans() == []
    paths = tel.save(str(tmp_path / "tel"))
    span_lines = [json.loads(l) for l in open(paths["spans"])]
    assert span_lines == [versioned({"stream": "spans"})]
    trace = json.load(open(paths["trace"]))
    assert [e for e in trace["traceEvents"] if e["ph"] == "X"] == []


# ---------------------------------------------------------------------------
# engine / checkpoint instrumentation (in-process, tiny model)
# ---------------------------------------------------------------------------


def _tiny_engine(**kw):
    import jax
    from repro.configs.base import ModelConfig
    from repro.launch.serve import ContinuousBatchingEngine
    from repro.models.registry import build_model
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      tie_embeddings=True, source="test")
    model = build_model(cfg, impl="naive")
    params = model.init(jax.random.PRNGKey(0))
    return ContinuousBatchingEngine(model, params, **kw)


def test_cow_and_prefill_spans_recorded():
    from repro.launch.serve import Request
    tel = obs.set_telemetry(obs.Telemetry(enabled=True)) and obs.get_telemetry()
    prompt = np.arange(3, 13, dtype=np.int32)  # partial tail block
    engine = _tiny_engine(max_batch=3, max_seq=32, kv_layout="paged",
                          block_size=4)
    engine.run([Request(uid=i, prompt=prompt.copy(), max_new_tokens=4)
                for i in range(3)])
    assert engine.stats()["cow_copies"] > 0
    agg = tel.tracer.by_name()
    assert agg["serve.cow_copy"]["count"] == engine.stats()["cow_copies"]
    assert agg["serve.prefill_chunk"]["count"] > 0
    assert agg["serve.decode"]["count"] == engine.decode_steps


def test_decode_span_holds_its_wait_and_queue_counters():
    from repro.launch.serve import Request
    tel = obs.set_telemetry(obs.Telemetry(enabled=True)) and obs.get_telemetry()
    engine = _tiny_engine(max_batch=2, max_seq=32, kv_layout="paged",
                          block_size=4)
    n = 3
    done = engine.run([Request(uid=i, prompt=np.arange(2, 9, dtype=np.int32),
                               max_new_tokens=4) for i in range(n)])
    spans = tel.tracer.spans()
    decodes = [s for s in spans if s.name == "serve.decode"]
    waits = [s for s in spans if s.name == "serve.decode_wait"]
    assert len(decodes) == engine.decode_steps > 0
    assert len(waits) == len(decodes)
    for d, w in zip(decodes, waits):
        assert w.depth == d.depth + 1
        assert d.ts_us <= w.ts_us
        assert w.ts_us + w.dur_us <= d.ts_us + d.dur_us
    agg = tel.tracer.by_name()
    assert agg["serve.admit"]["count"] == agg["serve.emit"]["count"] == \
        engine.decode_steps
    assert engine.admitted == len(done) == n
    assert engine.queue_wait_s >= 0.0
    st = engine.stats()
    assert st["admitted"] == n and st["queue_wait_s"] == engine.queue_wait_s


def test_swap_spans_recorded():
    from repro.launch.serve import Request
    tel = obs.set_telemetry(obs.Telemetry(enabled=True)) and obs.get_telemetry()
    engine = _tiny_engine(max_batch=2, max_seq=32, kv_layout="paged",
                          block_size=4, num_blocks=6,
                          admission_policy="swap", prefix_cache=False)
    engine.run([Request(uid=i, prompt=np.arange(2, 10, dtype=np.int32),
                        max_new_tokens=4) for i in range(3)])
    st = engine.stats()
    assert st["swap_outs"] >= 1
    agg = tel.tracer.by_name()
    assert agg["serve.swap_out"]["count"] == st["swap_outs"]
    assert agg["serve.swap_in"]["count"] == st["swap_ins"]


def test_checkpoint_spans_recorded(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    tel = obs.set_telemetry(obs.Telemetry(enabled=True)) and obs.get_telemetry()
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": np.ones(4, np.float32)}
    mgr.save(3, state)
    step, restored = mgr.restore_latest()
    assert step == 3 and np.array_equal(restored["w"], state["w"])
    agg = tel.tracer.by_name()
    assert agg["ckpt.save"]["count"] == 1
    assert agg["ckpt.restore"]["count"] == 1
    recs = {s.name: s for s in tel.tracer.spans()}
    assert recs["ckpt.save"].args == {"step": 3}


# ---------------------------------------------------------------------------
# end to end: one mixed run -> full bundle
# ---------------------------------------------------------------------------


def _metric_families(metrics_path):
    lines = [json.loads(l) for l in open(metrics_path)]
    body = [l for l in lines if "metrics" in l]
    assert lines[0]["schema_version"] == SCHEMA_VERSION
    return body, {k.split("{")[0] for l in body for k in l["metrics"]}


def test_mixed_run_emits_complete_bundle(tmp_path):
    from repro.launch import mixed as M
    outdir = tmp_path / "tel"
    tl_out = tmp_path / "merged.json"
    json_out = tmp_path / "run.json"
    M.main(["--arch", "llama3.2-1b", "--reduced", "--ticks", "12",
            "--batch", "4", "--seq", "32", "--slots", "2",
            "--requests", "5", "--prompt-len", "8", "--gen", "6",
            "--kv-layout", "paged", "--battery-j", "200",
            "--thermal-trace", "0.3:0.25:3.0:0.5:0.4", "--quiet",
            "--telemetry-out", str(outdir), "--timeline-out", str(tl_out),
            "--json-out", str(json_out)])

    # (a) one metrics schema covering serve / train / pool / energy / thermal
    body, fams = _metric_families(outdir / "metrics.jsonl")
    assert len(body) >= 12  # one line per tick + final
    for fam in ["serve_tokens_out", "serve_occupancy", "train_loss",
                "train_steps_total", "pool_utilization", "pool_fragmentation",
                "pool_total_cow", "prefix_hit_rate", "energy_loan_j",
                "battery_level", "thermal_temp_c", "thermal_throttled",
                "job_rung_idx", "job_step_latency_s",
                "runtime_migrations_total"]:
        assert fam in fams, f"metric family {fam} missing from the stream"

    # (b) a Perfetto-loadable trace with the expected span vocabulary
    trace = json.load(open(outdir / "trace.json"))
    json.dumps(trace, allow_nan=False)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in xs}
    assert {"runtime.tick", "train.step", "serve.decode",
            "serve.prefill_chunk"} <= names
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    ticks = sorted(e["ts"] for e in xs if e["name"] == "runtime.tick")
    steps = [e for e in xs if e["name"] == "train.step"]
    assert len(ticks) == 12 and len(steps) >= 1
    assert any(e["args"].get("compile") for e in steps)  # warmup tagged

    # (c) every migration in the merged timeline has an audit record with
    # the scores that decided it
    tl = Timeline.from_json(json.load(open(tl_out)))
    assert tl.migrations, "thermal trace must force at least one migration"
    audit = obs.AuditLog.from_json(json.load(open(outdir / "audit.json")))
    commits = audit.commits()
    for m in tl.migrations:
        matches = [r for r in commits
                   if r.tick == m.step and r.job == m.job
                   and r.from_rung == m.from_rung and r.to_rung == m.to_rung]
        assert matches, f"no audit record for migration {m}"
        rec = matches[0]
        assert rec.rule == m.reason
        assert rec.scores, f"audit record for {m} carries no scores"
        assert rec.thermal is not None and rec.energy is not None
    assert any(r.event == "propose" for r in audit.records())

    # satellite: the ad-hoc CLI JSON now rides the same schema
    payload = json.load(open(json_out))
    assert payload["schema_version"] == SCHEMA_VERSION
    assert json.load(open(tl_out))["schema_version"] == SCHEMA_VERSION

    # obs_report consumes the bundle and re-derives a chrome trace
    from repro.launch import obs_report
    chrome2 = tmp_path / "chrome2.json"
    rep = obs_report.main([str(outdir), "--top", "5", "--audit-limit", "5",
                           "--chrome-trace", str(chrome2)])
    assert rep["spans"][0]["name"] == "runtime.tick"  # ticks dominate
    assert rep["final_metrics"]
    doc2 = json.load(open(chrome2))
    assert {e["name"] for e in doc2["traceEvents"] if e["ph"] == "X"} == names
