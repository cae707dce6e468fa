"""The paged decode kernel's roofline least time (the traced steps' live
KV) over its device time: the Pallas kernels inside the decode step."""
from bench.metrics import _share


def read(run):
    t = run.trace
    if t is None:
        return None
    k_s, n = t.kernel_s(_share.DECODE)
    work = run.record["work"]["paged_attention"]
    if n == 0 or k_s <= 0 or work.bytes <= 0:
        return None
    return 100.0 * work.least_s(run.peaks) / k_s
