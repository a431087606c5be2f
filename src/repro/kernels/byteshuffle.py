"""Pallas TPU kernel: byte-plane shuffle (the `transpose` codec hot path).

(n, w) uint8 records -> (w, n) planes.  Tiled so each grid step transposes a
(BLOCK, w) VMEM tile into a (w, BLOCK) slab of the output — the classic
blocked transpose, with the record width w kept whole per tile (w <= 8 for
numeric streams, so a tile is ~16 KiB).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 2048


def _shuffle_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


def byteshuffle_pallas(x: jax.Array, *, interpret: bool) -> jax.Array:
    """x: (n, w) uint8 with n % BLOCK == 0 -> (w, n) uint8."""
    n, w = x.shape
    assert n % BLOCK == 0, "caller pads to BLOCK multiple"
    grid = (n // BLOCK,)
    return pl.pallas_call(
        _shuffle_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((w, BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((w, n), jnp.uint8),
        interpret=interpret,
    )(x)


def byteunshuffle_pallas(p: jax.Array, *, interpret: bool) -> jax.Array:
    """p: (w, n) uint8 planes -> (n, w) records (inverse)."""
    w, n = p.shape
    assert n % BLOCK == 0, "caller pads to BLOCK multiple"
    grid = (n // BLOCK,)
    return pl.pallas_call(
        _shuffle_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((w, BLOCK), lambda i: (0, i))],
        out_specs=pl.BlockSpec((BLOCK, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.uint8),
        interpret=interpret,
    )(p)
