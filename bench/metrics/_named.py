"""Device time of the Pallas kernels found by their name in the device
trace."""
from __future__ import annotations

import re
from typing import Tuple

from bench.metrics import _share


def train_kernel_s(trace, name: str) -> Tuple[float, int]:
    """Device seconds and calls of the ops inside the train step whose
    instruction is ``%<name>``, alone or followed by ``.`` (``%flash_fwd``,
    ``%flash_fwd.3``): the kernel whose ``pallas_call`` was given ``name``."""
    program = re.compile(_share.TRAIN_STEP)
    op = re.compile(rf"%{re.escape(name)}(\..*)?")
    keys = [k for k in trace.op_s
            if program.search(k[0]) and op.fullmatch(k[1])]
    return (sum(trace.op_s[k] for k in keys),
            sum(trace.op_calls[k] for k in keys))
