"""The forward and backward flash rooflines on a synthetic train-step
trace whose kernels carry their names."""
import json

import pytest
from jax.profiler import ProfileData

from bench import counts
from bench.harness import REPO, RunView, find
from bench.tests.test_bench_trace import _event, _plane
from bench.trace import reduce_profile

PALLAS = 'custom_call_target=\\"tpu_custom_call\\"'
BATCH, SEQ = 2, 2048


def train_trace(names):
    """Window 0..100 ms; one train step 0..90 ms with two layers' kernels:
    ``names`` gives the forward, dq and dkv instructions. Forward 3 + 4 ms,
    dq 5 + 6 ms, dkv 7 + 8 ms, and a fusion of 10 ms that is no kernel."""
    fwd, dq, dkv = names
    host = _plane(1, "/host:CPU", {1: "bench.trace_window"},
                  ['lines { id: 1 name: "python" timestamp_ns: 0 '
                   + _event(1, 0, 100) + " }"])
    metas = {11: f"{fwd}.3 = f32[8] custom-call(f32[8] %a), {PALLAS}",
             12: f"{fwd}.9 = f32[8] custom-call(f32[8] %a), {PALLAS}",
             13: f"{dq}.4 = f32[8] custom-call(f32[8] %a), {PALLAS}",
             14: f"{dkv}.5 = f32[8] custom-call(f32[8] %a), {PALLAS}",
             15: "%fusion.1 = f32[8] fusion(f32[8] %x)",
             21: "jit_train_step(4)"}
    ops = " ".join([_event(11, 1, 3), _event(12, 5, 4), _event(15, 10, 10),
                    _event(13, 30, 5), _event(14, 36, 7),
                    _event(13, 50, 6), _event(14, 57, 8)])
    dev = _plane(2, "/device:TPU:0", metas,
                 [f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 '
                  f'{_event(21, 0, 90)} }}',
                  f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }}'])
    return reduce_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host + " " + dev)))


NAMED = ("%flash_fwd", "%flash_dq", "%flash_dkv")


def _view(trace):
    cfg = json.loads((REPO / "bench/configs/granite-3-2b-train.json").read_text())
    return RunView(cfg=cfg, mix={}, spec=counts.Spec.from_config(cfg),
                   trace=trace, peaks=counts.load_peaks("TPU v5 lite"),
                   record={"batch": BATCH, "seq": SEQ})


def _read(name, view):
    return find(REPO, "metrics", name).read(view)


def test_forward_and_backward_shares_by_hand():
    view = _view(train_trace(NAMED))
    lf = counts.flash_forward(view.spec, BATCH, SEQ).least_s(view.peaks)
    lb = counts.flash_backward(view.spec, BATCH, SEQ).least_s(view.peaks)
    # two layer calls of each kernel
    assert _read("flash_forward_roofline", view) == \
        pytest.approx(100 * 2 * lf / 0.007)
    assert _read("flash_backward_roofline", view) == \
        pytest.approx(100 * 2 * lb / (0.011 + 0.015))


def test_the_two_recombine_to_the_lumped_roofline():
    view = _view(train_trace(NAMED))
    lf = counts.flash_forward(view.spec, BATCH, SEQ).least_s(view.peaks)
    lb = counts.flash_backward(view.spec, BATCH, SEQ).least_s(view.peaks)
    rf = _read("flash_forward_roofline", view)
    rb = _read("flash_backward_roofline", view)
    assert (lf + lb) / (lf / rf + lb / rb) == \
        pytest.approx(_read("flash_attention_roofline", view))


def test_unnamed_kernels_read_nothing():
    view = _view(train_trace(("%closed_call", "%closed_call", "%closed_call")))
    assert _read("flash_attention_roofline", view) is not None
    assert _read("flash_forward_roofline", view) is None
    assert _read("flash_backward_roofline", view) is None


def test_names_that_only_start_alike_are_not_the_kernel():
    view = _view(train_trace(("%flash_fwd_v2", "%flash_dq", "%flash_dkv")))
    assert _read("flash_forward_roofline", view) is None
    assert _read("flash_backward_roofline", view) is not None


def test_backward_reads_nothing_when_dq_and_dkv_calls_differ():
    view = _view(train_trace(("%flash_fwd", "%flash_dq", "%flash_dq_extra")))
    assert _read("flash_backward_roofline", view) is None
    view = _view(train_trace(("%flash_fwd", "%flash_dq", "%flash_dkv")))
    t = view.trace
    key = ("jit_train_step", "%flash_dkv.5")
    t.op_calls[key] += 1  # one dkv call more than dq
    assert _read("flash_backward_roofline", view) is None
