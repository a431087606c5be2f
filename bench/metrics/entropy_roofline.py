"""Entropy kernels (histogram_exact, huffman_map, fse_encode, pack_bits): the
codec work of their device nodes over peak HBM bandwidth, over the device time
of their compiled programs (profiler trace)."""
from bench.measure import roofline


def read(run):
    return roofline(run, "entropy")
