"""Fused decode chunk: device time of chunk executions in the traced slice
over the decode steps they ran."""
from . import DECODE
from ..trace import module_sum


def read(run, trace):
    steps = run["slice"]["decode_steps"]
    if trace is None or steps == 0:
        return None
    s = module_sum(trace, DECODE)
    return 1e3 * s / steps if s > 0 else None
