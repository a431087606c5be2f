"""Pallas TPU kernel: k-bit pack/unpack over uint32 words.

TPU restriction (DESIGN.md §2): k must divide 32 so values never straddle a
word.  The host codec keeps arbitrary-k support.

The encoder works on the lane-dense (rows, 128) view of the value stream
(a free reshape of the 1-D array: both layouts are 1024-element tiles).
Word ``j`` packs values ``j*per .. j*per+per-1``, which sit in ``per``
adjacent lanes of one row.  Per output row block, input row ``r*per + t``
holds lanes ``t*(128/per) ..`` of output row ``r``, so the kernel reads the
rows of each ``t`` with one sublane-strided load, ORs each lane group
together with log2(per) lane shifts (fields never overlap, so OR is the
sum), gathers the group heads into place with one in-vreg lane gather and
selects them into the output.  Only shapes, shifts, concatenations and
2-D lane gathers that Mosaic lowers are used: no unsigned reduction, no
in-kernel reshape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANES = 128
IN_ROWS = 512  # input rows of 128 values per grid step (256 KiB of u32)
BLOCK_VALS = IN_ROWS * LANES  # the encoders' padding multiple
BLOCK_WORDS = 512  # output words per grid step of the (1-D) unpack kernel


def pack_rows(rows_of, out_rows: int, bits: int) -> jax.Array:
    """Pack a block of values into (out_rows, 128) u32 words.

    ``rows_of(t)`` returns the (out_rows, 128) input rows ``r*per + t``
    (a strided load from a ref).  Shared with the fused delta+bitpack
    encoder."""
    per = 32 // bits
    groups = LANES // per  # words per input row
    lane = jax.lax.broadcasted_iota(jnp.int32, (out_rows, LANES), 1)
    shift = ((lane % per) * bits).astype(jnp.uint32)
    head = (lane % groups) * per  # first lane of the group this word packs
    out = jnp.zeros((out_rows, LANES), jnp.uint32)
    for t in range(per):
        w = rows_of(t) << shift
        s = 1
        while s < per:  # lane c ORs lanes c .. c+2s-1: group heads get all
            w = w | jnp.concatenate(
                [w[:, s:], jnp.zeros((out_rows, s), jnp.uint32)], axis=1
            )
            s *= 2
        words = jnp.take_along_axis(w, head, axis=1)
        out = jnp.where(lane // groups == t, words, out)
    return out


def _pack_kernel(bits: int):
    per = 32 // bits
    out_rows = IN_ROWS // per

    def kernel(x_ref, o_ref):
        o_ref[...] = pack_rows(
            lambda t: x_ref[pl.ds(t, out_rows, stride=per), :], out_rows, bits
        )

    return kernel


def _unpack_kernel(bits: int):
    per = 32 // bits
    mask = np.uint32((1 << bits) - 1)

    def kernel(w_ref, o_ref):
        shifts = (jnp.arange(per, dtype=jnp.uint32) * np.uint32(bits))
        w = w_ref[...]
        o_ref[...] = ((w[:, None] >> shifts[None, :]) & mask).reshape(-1)

    return kernel


def bitpack_pallas(x: jax.Array, bits: int, *, interpret: bool) -> jax.Array:
    """x: u32 values, size a multiple of BLOCK_VALS -> n/per packed words."""
    assert 32 % bits == 0, "TPU bitpack: bits must divide 32"
    per = 32 // bits
    n = x.shape[0]
    assert n % BLOCK_VALS == 0, "caller pads to block multiple"
    out_rows = IN_ROWS // per
    grid = (n // BLOCK_VALS,)
    out = pl.pallas_call(
        _pack_kernel(bits),
        grid=grid,
        in_specs=[pl.BlockSpec((IN_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((out_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // per // LANES, LANES), jnp.uint32),
        interpret=interpret,
    )(x.reshape(n // LANES, LANES))
    return out.reshape(-1)


def bitunpack_pallas(w: jax.Array, bits: int, *, interpret: bool) -> jax.Array:
    assert 32 % bits == 0
    per = 32 // bits
    m = w.shape[0]
    assert m % BLOCK_WORDS == 0, "caller pads to block multiple"
    grid = (m // BLOCK_WORDS,)
    return pl.pallas_call(
        _unpack_kernel(bits),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_WORDS,), lambda i: (i,))],
        out_specs=pl.BlockSpec((BLOCK_WORDS * per,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m * per,), jnp.uint32),
        interpret=interpret,
    )(w)
