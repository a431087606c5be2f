# Pallas TPU kernels for the codec hot-spots OpenZL optimizes in C
# (DESIGN.md §2): delta, byteshuffle (transpose), bitpack, float_split, the
# huffman symbol map, and the beyond-paper fused_delta_bitpack.  Each kernel
# module holds the pl.pallas_call + BlockSpec tiling; ops.py is the jit'd
# public wrapper (and the one place that picks kernel or oracle); ref.py is
# the pure-jnp oracle the tests sweep against.
from . import ops, ref  # noqa: F401
