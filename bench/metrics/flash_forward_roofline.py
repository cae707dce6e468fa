"""The flash forward kernel's roofline least time (its causal work at the
step's shapes) over its device time: the ``flash_fwd`` kernels inside the
train step, one per layer and step."""
from bench import counts
from bench.metrics import _named


def read(run):
    t = run.trace
    if t is None:
        return None
    f_s, n = _named.train_kernel_s(t, "flash_fwd")
    if n == 0 or f_s <= 0:
        return None
    least = counts.flash_forward(run.spec, run.record["batch"],
                                 run.record["seq"]).least_s(run.peaks)
    return 100.0 * n * least / f_s
