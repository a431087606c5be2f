"""Pallas TPU kernel: tANS (FSE) lane-parallel decode.

Encode has no Pallas kernel: its backward state walk steps through the
flattened encode table (up to 256 x 2^table_log entries), a gather that
Mosaic lowers only within one vreg.  ``ref.fse_encode_lanes`` runs it as
plain XLA on every backend (``ops.fse_encode``).

Decode is the forward walk: emit ``dec_sym[state]``, retreat the bit cursor,
refill a 32-bit window from the per-lane padded buffer (lane_refill gather
idiom) and gather the next state.  Exhausted lanes walk garbage states over
the zero pad — always in-table, trimmed by the caller, exactly like the
host's mask-free loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE_BLOCK = 256  # lanes per grid step


def _decode_kernel(
    lane_base_ref,
    bitlen_ref,
    state0_ref,
    flat_ref,
    sym_ref,
    nb_ref,
    base_ref,
    o_ref,
    *,
    max_rem,
):
    w32 = flat_ref[...].astype(jnp.uint32)
    sym = sym_ref[...].astype(jnp.int32)
    nbt = nb_ref[...]
    bst = base_ref[...]
    lane_base = lane_base_ref[...].astype(jnp.int32)

    def step(i, carry):
        state, cursor = carry
        o_ref[pl.ds(i, 1), :] = jnp.take(sym, state).astype(jnp.uint8)[None, :]
        nb = jnp.take(nbt, state)
        base = jnp.take(bst, state)
        cursor = cursor - nb
        byte0 = lane_base + jnp.maximum(cursor >> 3, 0)
        r = (cursor & 7).astype(jnp.uint32)
        b0 = jnp.take(w32, byte0)
        b1 = jnp.take(w32, byte0 + 1)
        b2 = jnp.take(w32, byte0 + 2)
        b3 = jnp.take(w32, byte0 + 3)
        b4 = jnp.take(w32, byte0 + 4)
        lo = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        win = (lo >> r) | ((b4 << 1) << (jnp.uint32(31) - r))
        bits = win & ((jnp.uint32(1) << nb.astype(jnp.uint32)) - jnp.uint32(1))
        return base + bits.astype(jnp.int32), cursor

    jax.lax.fori_loop(
        0,
        max_rem,
        step,
        (state0_ref[...].astype(jnp.int32), bitlen_ref[...].astype(jnp.int32)),
    )


def fse_decode_pallas(
    flat: jax.Array,
    lane_base: jax.Array,
    bitlen: jax.Array,
    state0: jax.Array,
    dec_sym: jax.Array,
    dec_nb: jax.Array,
    dec_base: jax.Array,
    max_rem: int,
    *,
    interpret: bool,
):
    """(flat u8 concatenated per-lane padded buffers, lane_base i32 byte
    offsets, bitlen i32 bit lengths, state0 i32 final states, decode tables
    2^table_log) -> (max_rem, n_lanes) u8 symbols."""
    n = bitlen.shape[0]
    assert n % LANE_BLOCK == 0, "caller pads lanes to LANE_BLOCK multiple"
    grid = (n // LANE_BLOCK,)
    tab = lambda a: pl.BlockSpec(a.shape, lambda i: (0,))
    return pl.pallas_call(
        functools.partial(_decode_kernel, max_rem=max_rem),
        grid=grid,
        in_specs=[
            pl.BlockSpec((LANE_BLOCK,), lambda i: (i,)),
            pl.BlockSpec((LANE_BLOCK,), lambda i: (i,)),
            pl.BlockSpec((LANE_BLOCK,), lambda i: (i,)),
            tab(flat),
            tab(dec_sym),
            tab(dec_nb),
            tab(dec_base),
        ],
        out_specs=pl.BlockSpec((max_rem, LANE_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((max_rem, n), jnp.uint8),
        interpret=interpret,
    )(lane_base, bitlen, state0, flat, dec_sym, dec_nb, dec_base)
