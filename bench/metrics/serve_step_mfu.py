"""Model FLOPs of the traced prefill chunks and decode steps over those
programs' device time, as a share of the chip's bf16 peak."""
from bench.metrics import _share


def read(run):
    return _share.serve_step_mfu(run)
