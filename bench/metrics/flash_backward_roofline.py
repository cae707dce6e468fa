"""The flash backward kernels' roofline least time (their causal work at
the step's shapes) over their device time: the ``flash_dq`` and
``flash_dkv`` kernels inside the train step, one of each per layer and
step. Nothing is read where the two kernels' calls differ."""
from bench import counts
from bench.metrics import _named


def read(run):
    t = run.trace
    if t is None:
        return None
    dq_s, n = _named.train_kernel_s(t, "flash_dq")
    dkv_s, n_dkv = _named.train_kernel_s(t, "flash_dkv")
    if n == 0 or n != n_dkv or dq_s + dkv_s <= 0:
        return None
    least = counts.flash_backward(run.spec, run.record["batch"],
                                  run.record["seq"]).least_s(run.peaks)
    return 100.0 * n * least / (dq_s + dkv_s)
