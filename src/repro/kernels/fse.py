"""Pallas TPU kernels: tANS (FSE) lane-parallel encode walk and decode.

Encode is the backward walk over FSE's compact ``stateTable``
(``codecs/entropy.py`` ``_build_tables``): 2^table_log entries, where the
padded per-symbol encode table holds 256 x max(norm).  One grid step walks
ENC_CHUNK positions of ENC_ROWS x 128 lanes; the lane states stay in one
vreg and the state table in VMEM as (2^table_log / 128, 128) rows, so each
step's next-state gather ``state_table[(X >> nb) + delta[s]]`` is one
in-vreg lane gather per table row and a select (the same idiom as
``huffman_map``).  Per-symbol helpers (``nb0 | thr << 5``, ``delta``) are
(2, 128) tables read the same way.  The step writes its emitted bits and
their count; offsets and packing are XLA glue in ``ops.fse_encode`` and
``pack_bits``.  ``ref.fse_encode_lanes`` is the jnp oracle.

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

LANES = 128
ENC_ROWS = 8  # lane rows of 128 per encode grid step: one int32 vreg
ENC_LANES = ENC_ROWS * LANES  # the encode's lane padding multiple
ENC_CHUNK = 128  # positions per encode grid step


def _lane_lookup(tab: jax.Array, idx: jax.Array) -> jax.Array:
    """``tab.reshape(-1)[idx]`` for a (rows, 128) table and an in-range
    (8, 128) idx: one in-vreg lane gather per table row, then a select."""
    lo = idx & (LANES - 1)
    hi = idx >> 7
    out = None
    for k in range(tab.shape[0]):
        cand = jnp.take_along_axis(
            jnp.broadcast_to(tab[k : k + 1], idx.shape), lo, axis=1
        )
        out = cand if out is None else jnp.where(hi == k, cand, out)
    return out


def _encode_kernel(
    sym_ref,
    rem_ref,
    init_ref,
    nbthr_ref,
    delta_ref,
    stab_ref,
    vals_ref,
    nbs_ref,
    state_ref,
    *,
    total,
):
    c = pl.program_id(1)
    base = (pl.num_programs(1) - 1 - c) * ENC_CHUNK

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    rem = rem_ref[...]
    init = init_ref[...]
    nbthr = nbthr_ref[...]
    dtab = delta_ref[...]
    stab = stab_ref[...]

    def step(j, state):
        r = ENC_CHUNK - 1 - j
        i1 = base + r + 1
        s = sym_ref[r]
        packed = _lane_lookup(nbthr, s)
        X = state + total
        nb = (packed & 31) - (X < (packed >> 5)).astype(jnp.int32)
        nxt = _lane_lookup(
            stab, jnp.clip((X >> nb) + _lane_lookup(dtab, s), 0, total - 1)
        )
        emit = rem > i1
        nbe = jnp.where(emit, nb, 0)
        nbs_ref[r] = nbe
        vals_ref[r] = X & ((1 << nbe) - 1)
        return jnp.where(emit, nxt, jnp.where(rem == i1, init, state))

    state_ref[...] = jax.lax.fori_loop(0, ENC_CHUNK, step, state_ref[...])


def fse_encode_pallas(
    sym: jax.Array,
    rem: jax.Array,
    init: jax.Array,
    nbthr: jax.Array,
    delta: jax.Array,
    state_table: jax.Array,
    *,
    interpret: bool,
):
    """tANS backward walk: (sym i32 (max_rem, rows, 128) lane symbols, rem
    and init i32 (rows, 128) lane lengths and start states, nbthr i32[256]
    ``nb0 | thr << 5``, delta i32[256], state_table i32[2^table_log]) ->
    (vals, nbits) i32 (max_rem, rows, 128) and final states i32 (rows, 128).

    A grid step walks ENC_CHUNK positions of ENC_ROWS x 128 lanes, the
    states in one vreg, the tables in VMEM as (rows, 128) for in-vreg lane
    gathers; the chunk axis runs backward and the state output block stays
    resident across it as the carry."""
    max_rem, rows, _ = sym.shape
    assert rows % ENC_ROWS == 0 and max_rem % ENC_CHUNK == 0
    total = state_table.shape[0]
    stab = jnp.pad(state_table, (0, (-total) % LANES)).reshape(-1, LANES)
    n_chunks = max_rem // ENC_CHUNK
    grid = (rows // ENC_ROWS, n_chunks)
    plane = pl.BlockSpec(
        (ENC_CHUNK, ENC_ROWS, LANES), lambda b, c: (n_chunks - 1 - c, b, 0)
    )
    lane = pl.BlockSpec((ENC_ROWS, LANES), lambda b, c: (b, 0))
    tab = lambda a: pl.BlockSpec(a.shape, lambda b, c: (0, 0))
    nbthr = nbthr.reshape(2, LANES)
    delta = delta.reshape(2, LANES)
    return pl.pallas_call(
        functools.partial(_encode_kernel, total=total),
        grid=grid,
        in_specs=[plane, lane, lane, tab(nbthr), tab(delta), tab(stab)],
        out_specs=[plane, plane, lane],
        out_shape=[
            jax.ShapeDtypeStruct(sym.shape, jnp.int32),
            jax.ShapeDtypeStruct(sym.shape, jnp.int32),
            jax.ShapeDtypeStruct(rem.shape, jnp.int32),
        ],
        interpret=interpret,
    )(sym, rem, init, nbthr, delta, stab)


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
