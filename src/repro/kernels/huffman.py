"""Pallas TPU kernels: canonical Huffman encode map + lane-parallel decode.

Encode on the device is table gathers plus bit packing: ``huffman_map``
turns symbols into (canonical code, length) pairs, and the shared
scatter-add packer (``ref.pack_bits`` / ops glue) places them at their
cumsum bit offsets.  The map kernel here is the gather, restated as
in-vreg lane gathers over a (2, 128) table layout; packing stays in XLA
(scatter-add has no Pallas win).

Decode is the lane-refill loop made device-resident: each lane gathers the
five bytes straddling its cursor, stitches a 32-bit LSB-first window
(lane_refill idiom), indexes the low 15 bits into the decode LUT, and
advances.  One symbol per refill — the host drains three per 64-bit window,
but decode output is the *symbols*, not the bitstream, so the twins agree
bit-exactly on everything wire-visible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
MAP_ROWS = 512  # rows of 128 symbols per grid step of the encode map
MAP_BLOCK = MAP_ROWS * LANES  # the map's padding multiple
LANE_BLOCK = 256  # lanes per grid step for decode


def _table_lookup(tab: jax.Array, x: jax.Array) -> jax.Array:
    """``tab[x]`` for a (2, 128) table of 256 entries and (rows, 128) x:
    two in-vreg lane gathers (one per table half) and a select."""
    lo = jnp.take_along_axis(
        jnp.broadcast_to(tab[0:1], x.shape), x & (LANES - 1), axis=1
    )
    hi = jnp.take_along_axis(
        jnp.broadcast_to(tab[1:2], x.shape), x & (LANES - 1), axis=1
    )
    return jnp.where(x >= LANES, hi, lo)


def _map_kernel(x_ref, codes_ref, lens_ref, code_ref, nbit_ref):
    xi = x_ref[...].astype(jnp.int32)
    code_ref[...] = _table_lookup(codes_ref[...], xi)
    nbit_ref[...] = _table_lookup(lens_ref[...], xi)


def huffman_map_pallas(
    x: jax.Array, codes: jax.Array, lens: jax.Array, *, interpret: bool
):
    """(x u8, codes u32[256], lens i32[256]) -> (code u32, nbits i32) per sym.

    Symbols go in as their lane-dense (rows, 128) view and the tables as
    (2, 128): Mosaic lowers only 2-D gathers within a vreg, never a 1-D
    ``jnp.take``."""
    n = x.shape[0]
    assert n % MAP_BLOCK == 0, "caller pads symbols to MAP_BLOCK multiple"
    rows = n // LANES
    grid = (n // MAP_BLOCK,)
    block = pl.BlockSpec((MAP_ROWS, LANES), lambda i: (i, 0))
    table = pl.BlockSpec((2, LANES), lambda i: (0, 0))
    code, nbit = pl.pallas_call(
        _map_kernel,
        grid=grid,
        in_specs=[block, table, table],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(x.reshape(rows, LANES), codes.reshape(2, LANES), lens.reshape(2, LANES))
    return code.reshape(n), nbit.reshape(n)


def _decode_kernel(pos_ref, buf_ref, sym_ref, len_ref, o_ref, *, max_rem):
    w32 = buf_ref[...].astype(jnp.uint32)
    sym = sym_ref[...].astype(jnp.int32)
    lnt = len_ref[...].astype(jnp.int32)

    def step(i, pos):
        byte0 = pos >> 3
        r = (pos & 7).astype(jnp.uint32)
        b0 = jnp.take(w32, byte0)
        b1 = jnp.take(w32, byte0 + 1)
        b2 = jnp.take(w32, byte0 + 2)
        b3 = jnp.take(w32, byte0 + 3)
        b4 = jnp.take(w32, byte0 + 4)
        lo = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        # (b4 << 1) << (31 - r) == b4 << (32 - r), well-defined at r == 0
        win = (lo >> r) | ((b4 << 1) << (jnp.uint32(31) - r))
        low = (win & jnp.uint32(0x7FFF)).astype(jnp.int32)
        o_ref[pl.ds(i, 1), :] = jnp.take(sym, low).astype(jnp.uint8)[None, :]
        return pos + jnp.take(lnt, low)

    jax.lax.fori_loop(0, max_rem, step, pos_ref[...].astype(jnp.int32))


def huffman_decode_pallas(
    buf: jax.Array,
    pos: jax.Array,
    lut_sym: jax.Array,
    lut_len: jax.Array,
    max_rem: int,
    *,
    interpret: bool,
):
    """(buf u8 padded >= 5 bytes past every cursor, pos i32 lane bit starts,
    lut_sym/lut_len 2^15 LUTs) -> (max_rem, n_lanes) u8 symbols."""
    n = pos.shape[0]
    assert n % LANE_BLOCK == 0, "caller pads lanes to LANE_BLOCK multiple"
    grid = (n // LANE_BLOCK,)
    return pl.pallas_call(
        functools.partial(_decode_kernel, max_rem=max_rem),
        grid=grid,
        in_specs=[
            pl.BlockSpec((LANE_BLOCK,), lambda i: (i,)),
            pl.BlockSpec(buf.shape, lambda i: (0,)),  # whole bitstream
            pl.BlockSpec(lut_sym.shape, lambda i: (0,)),  # whole decode LUTs
            pl.BlockSpec(lut_len.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((max_rem, LANE_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((max_rem, n), jnp.uint8),
        interpret=interpret,
    )(pos, buf, lut_sym, lut_len)
