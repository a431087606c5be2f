"""Pallas TPU kernel: entropy-lane window refill (gather-based).

The lane-parallel entropy decoders (``repro.codecs.entropy``) advance one
bit cursor per lane and refill a window register from the bitstream every
step.  On the host that refill is a single numpy sliding-window gather; this
kernel is the device twin: for each lane it gathers the five bytes straddling
the cursor and stitches them into a 32-bit LSB-first window (32 bits is two
max-length Huffman codes' worth, and TPU lanes have no native 64-bit ints —
DESIGN.md §2, so the device window is half the host's 64-bit one).

The gather (``jnp.take``) *is* the kernel: entropy refill is bandwidth-bound,
which is why it is worth keeping on-device next to the rest of a fused decode
pipeline instead of round-tripping windows through the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 256  # lanes per grid step


def _refill_kernel(pos_ref, buf_ref, o_ref):
    w32 = buf_ref[...].astype(jnp.uint32)
    pos = pos_ref[...].astype(jnp.int32)
    byte0 = pos >> 3
    r = ((pos & 7).astype(jnp.uint32))
    b0 = jnp.take(w32, byte0)
    b1 = jnp.take(w32, byte0 + 1)
    b2 = jnp.take(w32, byte0 + 2)
    b3 = jnp.take(w32, byte0 + 3)
    b4 = jnp.take(w32, byte0 + 4)
    lo = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
    # (b4 << 1) << (31 - r) == b4 << (32 - r), well-defined at r == 0
    o_ref[...] = (lo >> r) | ((b4 << 1) << (jnp.uint32(31) - r))


def lane_refill_pallas(
    buf: jax.Array, bitpos: jax.Array, *, interpret: bool
) -> jax.Array:
    """(buf u8, padded past every cursor by >= 5 bytes; bitpos i32) -> u32."""
    n = bitpos.shape[0]
    assert n % BLOCK == 0, "caller pads lanes to BLOCK multiple"
    grid = (n // BLOCK,)
    return pl.pallas_call(
        _refill_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec(buf.shape, lambda i: (0,)),  # whole bitstream
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
    )(bitpos, buf)
