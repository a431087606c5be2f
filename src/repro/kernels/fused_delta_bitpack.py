"""Pallas TPU kernel: FUSED delta + bitpack (beyond-paper optimization).

The paper's modular graph executes `delta` then `bitpack` as two codecs —
two HBM round-trips.  On TPU the stream transform is bandwidth-bound
(arithmetic intensity ≈ 0.5 flop/byte), so fusing them halves HBM traffic:

    baseline  : read x, write d      (delta)   + read d, write packed
              = 2n reads + n + n/per writes
    fused     : read x (+ one 8-row tile per block), write packed
              ≈ n reads + n/per writes                (~2x traffic cut)

The kernel computes the deltas of a (rows, 128) block into VMEM scratch and
packs them with the bitpack kernel's lane-dense ``pack_rows``.

Encode-only fusion is lossless for monotone streams whose deltas fit `bits`
(sorted indices, offset tables — exactly the paper's delta use cases); the
ops.py wrapper verifies the precondition.  See EXPERIMENTS.md §Perf/K1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitpack import BLOCK_VALS, BLOCK_WORDS, IN_ROWS, LANES, pack_rows

TAIL_ROWS = 8  # one u32 tile: the rows of the previous block each step reads


def _fused_encode_kernel(bits: int):
    per = 32 // bits
    mask = np.uint32((1 << bits) - 1)
    out_rows = IN_ROWS // per

    def kernel(x_ref, xprev_ref, o_ref, d_ref):
        # flat predecessor of every value in the (rows, 128) view: the row
        # above supplies lane 0 (the previous block's last row for row 0)
        x = x_ref[...]
        last = jnp.where(
            pl.program_id(0) == 0,
            jnp.uint32(0),
            xprev_ref[pl.ds(TAIL_ROWS - 1, 1), :],
        )
        above = jnp.concatenate([last, x[:-1]], axis=0)
        prev = jnp.concatenate([above[:, LANES - 1 :], x[:, :-1]], axis=1)
        d_ref[...] = (x - prev) & mask
        o_ref[...] = pack_rows(
            lambda t: d_ref[pl.ds(t, out_rows, stride=per), :], out_rows, bits
        )

    return kernel


def fused_delta_bitpack_pallas(
    x: jax.Array, bits: int, *, interpret: bool
) -> jax.Array:
    """x: u32 values, size a multiple of BLOCK_VALS -> n/per packed words."""
    assert 32 % bits == 0
    per = 32 // bits
    n = x.shape[0]
    assert n % BLOCK_VALS == 0, "caller pads to block multiple"
    out_rows = IN_ROWS // per
    grid = (n // BLOCK_VALS,)
    x2 = x.reshape(n // LANES, LANES)
    out = pl.pallas_call(
        _fused_encode_kernel(bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((IN_ROWS, LANES), lambda i: (i, 0)),
            # the same array: the last rows of the previous block (clamped)
            pl.BlockSpec(
                (TAIL_ROWS, LANES),
                lambda i: (jnp.maximum(i * (IN_ROWS // TAIL_ROWS) - 1, 0), 0),
            ),
        ],
        out_specs=pl.BlockSpec((out_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // per // LANES, LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((IN_ROWS, LANES), jnp.uint32)],
        interpret=interpret,
    )(x2, x2)
    return out.reshape(-1)


def _fused_decode_sum_kernel(bits: int):
    per = 32 // bits
    mask = np.uint32((1 << bits) - 1)

    def kernel(w_ref, o_ref):
        shifts = jnp.arange(per, dtype=jnp.uint32) * np.uint32(bits)
        w = w_ref[...]
        d = ((w[:, None] >> shifts[None, :]) & mask).reshape(-1)
        o_ref[...] = jnp.sum(d, dtype=jnp.uint32)[None]

    return kernel


def _fused_decode_scan_kernel(bits: int):
    per = 32 // bits
    mask = np.uint32((1 << bits) - 1)

    def kernel(w_ref, carry_ref, o_ref):
        shifts = jnp.arange(per, dtype=jnp.uint32) * np.uint32(bits)
        w = w_ref[...]
        d = ((w[:, None] >> shifts[None, :]) & mask).reshape(-1)
        o_ref[...] = jnp.cumsum(d, dtype=jnp.uint32) + carry_ref[0]

    return kernel


def fused_delta_bitpack_decode_pallas(
    w: jax.Array, bits: int, *, interpret: bool
) -> jax.Array:
    """Fused unpack+scan decode: packed words are read twice (sum pass + scan
    pass) but the full-width delta stream never touches HBM at all."""
    assert 32 % bits == 0
    per = 32 // bits
    m = w.shape[0]
    assert m % BLOCK_WORDS == 0
    grid = (m // BLOCK_WORDS,)
    in_spec = pl.BlockSpec((BLOCK_WORDS,), lambda i: (i,))
    sums = pl.pallas_call(
        _fused_decode_sum_kernel(bits),
        grid=grid,
        in_specs=[in_spec],
        out_specs=pl.BlockSpec((1,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m // BLOCK_WORDS,), jnp.uint32),
        interpret=interpret,
    )(w)
    carry = jnp.cumsum(sums, dtype=jnp.uint32) - sums
    return pl.pallas_call(
        _fused_decode_scan_kernel(bits),
        grid=grid,
        in_specs=[in_spec, pl.BlockSpec((1,), lambda i: (i,))],
        out_specs=pl.BlockSpec((BLOCK_WORDS * per,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m * per,), jnp.uint32),
        interpret=interpret,
    )(w, carry)
