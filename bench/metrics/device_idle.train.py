"""Share of the traced window in which no operation ran on the device."""
from bench.metrics import _share


def read(run):
    return _share.idle_percent(run)
