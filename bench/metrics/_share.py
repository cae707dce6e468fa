"""Arithmetic the per-layer readers share."""
from __future__ import annotations

# program names in the device trace (the jitted functions' names); the
# Pallas kernels are found inside them (their calls carry no name yet)
TRAIN_STEP = r"^jit_train_step$"
DECODE = r"^jit_decode_step$"
PREFILL = r"^jit_prefill_chunk$"


def idle_percent(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def serve_step_mfu(run):
    """Model FLOPs of the traced prefill chunks and decode steps over their
    programs' device time, as a share of the chip's peak."""
    t = run.trace
    if t is None:
        return None
    work = run.record["work"]
    flops = work["decode"].flops + work["prefill"].flops
    dec_s, _ = t.program_s(DECODE)
    pre_s, _ = t.program_s(PREFILL)
    if flops <= 0 or dec_s + pre_s <= 0:
        return None
    return 100.0 * flops / (dec_s + pre_s) / run.peaks["bf16_flops_per_s"]
