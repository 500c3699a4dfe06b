"""Prefill program: device time of prefill executions in the traced slice
per 1000 real (unpadded) prompt tokens admitted in it."""
from . import PREFILL
from ..trace import module_sum


def read(run, trace):
    toks = run["slice"]["prompt_tokens"]
    if trace is None or toks == 0:
        return None
    s = module_sum(trace, PREFILL)
    return 1e3 * s / (toks / 1e3) if s > 0 else None
