"""Pallas TPU kernels: blocked delta encode/decode.

OpenZL's CPU delta kernel is a byte-serial scan.  The TPU adaptation
(DESIGN.md §2.2) splits the stream into VMEM-sized blocks:

  encode  — embarrassingly parallel; the cross-block neighbour is read from a
            second ref mapped to block i-1 (clamped at 0, masked).
  decode  — decoupled scan: (1) per-block sums, (2) tiny exclusive cumsum on
            the host program, (3) per-block inclusive scan + carry add.

All arithmetic is wrapping uint32 — bit-exact with the host numpy codec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 2048  # 8 KiB of u32 per ref — comfortably inside 16 MiB VMEM


def _encode_kernel(x_ref, xprev_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...]
    prev_last = jnp.where(i == 0, jnp.uint32(0), xprev_ref[BLOCK - 1])
    shifted = jnp.concatenate([prev_last[None], x[:-1]])
    o_ref[...] = x - shifted


def delta_encode_pallas(x: jax.Array, *, interpret: bool) -> jax.Array:
    n = x.shape[0]
    assert n % BLOCK == 0, "caller pads to BLOCK multiple"
    grid = (n // BLOCK,)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            # the same array, mapped to the previous block (clamped at 0)
            pl.BlockSpec((BLOCK,), lambda i: (jnp.maximum(i - 1, 0),)),
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
    )(x, x)


def _block_sum_kernel(x_ref, o_ref):
    o_ref[...] = jnp.sum(x_ref[...], dtype=jnp.uint32)[None]


def _scan_carry_kernel(x_ref, carry_ref, o_ref):
    o_ref[...] = jnp.cumsum(x_ref[...], dtype=jnp.uint32) + carry_ref[0]


def delta_decode_pallas(d: jax.Array, *, interpret: bool) -> jax.Array:
    n = d.shape[0]
    assert n % BLOCK == 0, "caller pads to BLOCK multiple"
    grid = (n // BLOCK,)
    sums = pl.pallas_call(
        _block_sum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n // BLOCK,), jnp.uint32),
        interpret=interpret,
    )(d)
    carry = jnp.cumsum(sums, dtype=jnp.uint32) - sums  # exclusive prefix
    return pl.pallas_call(
        _scan_carry_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
    )(d, carry)
