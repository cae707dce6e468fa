"""The flash kernels' roofline least time (their causal work at the step's
shapes) over their device time: the Pallas kernels inside the train step,
one forward, one dq and one dkv per layer and step."""
from bench import counts
from bench.metrics import _share


def read(run):
    t = run.trace
    if t is None:
        return None
    k_s, n = t.kernel_s(_share.TRAIN_STEP)
    if n == 0 or k_s <= 0:
        return None
    b, s = run.record["batch"], run.record["seq"]
    per_layer = counts.flash_forward(run.spec, b, s).least_s(run.peaks) \
        + counts.flash_backward(run.spec, b, s).least_s(run.peaks)
    return 100.0 * (n / 3) * per_layer / k_s
