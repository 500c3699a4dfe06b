"""Model step: 2 x kept GEMM parameters x decode tokens of the traced
slice, over the decode programs' device time x the chip's bf16 peak x the
chips.  Decode tokens are the tokens the slice emitted less the first
tokens of its admissions, which come from prefill."""
from . import DECODE
from ..trace import module_sum
from ..work import decode_step


def read(run, trace):
    s = run["slice"]
    toks = s["emitted"] - s["admitted"]
    if trace is None or toks <= 0:
        return None
    dev = module_sum(trace, DECODE)
    if dev <= 0:
        return None
    params = decode_step(run["conf"])["params"]
    return 100.0 * 2.0 * params * toks / (
        dev * run["peak"]["bf16_flops"] * run["chips"])
