"""Model FLOPs of the window's steps over the window's host-clock time, as a
share of the chip's bf16 peak."""
from bench import counts


def read(run):
    r = run.record
    if not r.get("steps"):
        return None
    flops = counts.train_step_flops(run.spec, r["batch"], r["seq"]) * r["steps"]
    return 100.0 * flops / r["window_s"] / run.peaks["bf16_flops_per_s"]
