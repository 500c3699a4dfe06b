"""Mesh: device time of collective ops inside the decode programs of the
traced slice, per chip and decode step."""
from . import DECODE
from ..trace import module_sum


def read(run, trace):
    steps = run["slice"]["decode_steps"]
    if trace is None or steps == 0 or trace["devices"] < 2:
        return None
    return 1e3 * module_sum(trace, DECODE, "collective_s") / steps
