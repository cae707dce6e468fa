"""The trace reduction on a small synthetic trace."""
import pytest
from jax.profiler import ProfileData

from bench import counts
from bench.harness import RunView, find, REPO
from bench.trace import reduce_profile

MS = 1_000_000_000  # picoseconds in a millisecond


def _event(meta, start_ms, dur_ms, stats=""):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} {stats} }}")


def _plane(pid, name, metas, lines, stat_metas=""):
    md = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
                  for k, v in metas.items())
    return f'planes {{ id: {pid} name: "{name}" {" ".join(lines)} {md} {stat_metas} }}'


def _mod(name):
    return f'stats {{ metadata_id: 1 str_value: "{name}" }}'


def synthetic(module_line=True):
    """Window 0..100 ms. Device: a decode program 10..30 ms (two fusions
    and a Pallas kernel), a prefill program 40..60 ms (one op 50..60); the
    host is in bench.step 0..70 ms and waits 70..100 ms."""
    host = _plane(1, "/host:CPU", {1: "bench.trace_window", 2: "bench.step",
                                   3: "bench.wait_for_arrival", 4: "other"},
                  ['lines { id: 1 name: "python" timestamp_ns: 0 ' +
                   _event(1, 0, 100) + _event(2, 0, 70) + _event(3, 70, 30) +
                   _event(4, 5, 1) + " }"])
    ops = " ".join([
        _event(11, 10, 12, _mod("jit_decode_step")),
        _event(12, 20, 10, _mod("jit_decode_step")),   # overlaps the first
        _event(13, 15, 5, _mod("jit_decode_step")),    # the kernel
        _event(14, 50, 10, _mod("jit_prefill_chunk")),
        _event(14, 120, 10, _mod("jit_prefill_chunk")),  # after the window
    ])
    mods = " ".join([_event(21, 10, 20), _event(22, 40, 20)])
    kernel = ('%closed_call.13 = f32[8,8,4,64] custom-call(s32[8] %a), '
              'custom_call_target=\\"tpu_custom_call\\"')
    dev = _plane(2, "/device:TPU:0",
                 {11: "%fusion.1 = f32[8] fusion(f32[8] %x)",
                  12: "%fusion.2 = f32[8] fusion(f32[8] %y)", 13: kernel,
                  14: "%convolution.3 = f32[8] convolution(f32[8] %z)",
                  21: "jit_decode_step(7)", 22: "jit_prefill_chunk(9)"},
                 ([f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {mods} }}']
                  if module_line else [])
                 + [f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }}'],
                 'stat_metadata { key: 1 value { id: 1 name: "hlo_module" } }')
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host + " " + dev))


def test_reduction_of_a_small_trace():
    t = reduce_profile(synthetic())
    assert t.window_s == pytest.approx(0.1)
    assert t.n_chips == 1
    # busy is the union: 10..30 and 50..60
    assert t.busy_s == pytest.approx(0.03)
    assert t.program_s(r"^jit_decode_step$") == (pytest.approx(0.02), 1)
    assert t.program_s(r"^jit_prefill_chunk$") == (pytest.approx(0.02), 1)
    assert t.kernel_s(r"^jit_decode_step$") == (pytest.approx(0.005), 1)
    assert t.kernel_s(r"^jit_prefill_chunk$") == (0, 0)
    assert t.idle_gaps == [("bench.wait_for_arrival", pytest.approx(0.04)),
                           ("bench.step", pytest.approx(0.02)),
                           ("bench.step", pytest.approx(0.01))]
    b = t.breakdown()
    assert b["device_ops"][0] == ["jit_decode_step:%fusion.1", pytest.approx(0.012)]
    assert ["jit_decode_step:%closed_call.13 (pallas)", pytest.approx(0.005)] \
        in b["device_ops"]
    assert len(b["idle_gaps"]) == 3


def test_programs_from_op_stats_without_a_module_line():
    t = reduce_profile(synthetic(module_line=False))
    # ops attributed by their hlo_module stat; overlaps count twice
    assert t.program_s(r"^jit_decode_step$")[0] == pytest.approx(0.027)
    assert t.program_s(r"^jit_prefill_chunk$")[0] == pytest.approx(0.01)
    assert t.kernel_s(r"^jit_decode_step$") == (pytest.approx(0.005), 1)
    assert t.busy_s == pytest.approx(0.03)


def test_trace_without_window_or_device_is_refused():
    host = _plane(1, "/host:CPU", {1: "bench.step"},
                  ['lines { id: 1 name: "python" ' + _event(1, 0, 10) + " }"])
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host))
    with pytest.raises(ValueError):
        reduce_profile(pd)


def test_readers_on_the_synthetic_trace():
    t = reduce_profile(synthetic())
    import json
    cfg = json.loads((REPO / "bench/configs/granite-3-2b-serve.json").read_text())
    spec = counts.Spec.from_config(cfg)
    peaks = counts.load_peaks("TPU v5 lite")
    work = {"decode": counts.decode_step(spec, [100, 200]),
            "prefill": counts.prefill_chunk(spec, 0, 16),
            "paged_attention": counts.paged_decode_attention(spec, [100, 200])}
    view = RunView(cfg=cfg, mix={}, spec=spec, trace=t, peaks=peaks,
                   record={"work": work, "counters": {
                       "prefill_chunks": 3, "prefill_chunks_skipped": 9}})
    read = lambda name: find(REPO, "metrics", name).read(view)  # noqa: E731
    assert read("device_idle.serve") == pytest.approx(70.0)
    assert read("serve.prefill_share") == pytest.approx(100 * 0.02 / 0.03)
    assert read("serve.prefix_hit_share") == pytest.approx(75.0)
    flops = work["decode"].flops + work["prefill"].flops
    assert read("serve_step_mfu") == pytest.approx(100 * flops / 0.04 / 197e12)
    assert read("paged_attention_roofline") == pytest.approx(
        100 * work["paged_attention"].least_s(peaks) / 0.005)
    # no flash kernel in this trace: the reader finds nothing and says so
    assert find(REPO, "metrics", "flash_attention_roofline").read(
        RunView(cfg=cfg, mix={}, spec=spec, trace=t, peaks=peaks,
                record={"batch": 2, "seq": 2048})) is None
