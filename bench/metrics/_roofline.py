"""Shared arithmetic of the kernels' roofline shares: the decode steps of
the traced slice times one step's GEMM lower bound per chip
(``bench.work``), over the device time of one kernel's events inside the
decode programs, per chip."""
from . import DECODE
from ..trace import kernel_sum
from ..work import lower_bound_s


def share(run, trace, kernel: str):
    steps = run["slice"]["decode_steps"]
    if trace is None or steps == 0:
        return None
    t = kernel_sum(trace, DECODE, kernel)
    if t <= 0:
        return None
    return 100.0 * steps * lower_bound_s(run["conf"], run["peak"]) / t
