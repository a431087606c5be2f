"""Numeric kernels (delta, bitpack, fused delta+bitpack, byteshuffle,
float_split): the codec work of their device nodes over peak HBM bandwidth,
over the device time of their compiled programs (profiler trace)."""
from bench.measure import roofline


def read(run):
    return roofline(run, "numeric")
