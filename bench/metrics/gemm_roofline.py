"""Kernels: dense_gemm's share of its roofline over the decode steps."""
from ._roofline import share


def read(run, trace):
    return share(run, trace, "dense_gemm")
