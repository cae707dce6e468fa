"""Device time of the prefill-chunk programs over the device's busy time."""
from bench.metrics import _share


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    pre_s, _ = t.program_s(_share.PREFILL)
    return 100.0 * pre_s / t.busy_s
