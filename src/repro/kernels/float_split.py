"""Pallas TPU kernel: float plane split (checkpoint-compression hot path).

Splits uint32 float bit patterns into sign/exponent/mantissa planes in one
VMEM pass — the paper's §VIII checkpoint transform.  The multi-output
pallas_call produces all three planes from a single HBM read of the input.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK = 2048


def _split_kernel(exp_bits: int, man_bits: int):
    exp_mask = np.uint32((1 << exp_bits) - 1)
    man_mask = np.uint32((1 << man_bits) - 1)

    def kernel(u_ref, sign_ref, exp_ref, man_ref):
        u = u_ref[...]
        sign_ref[...] = (u >> (exp_bits + man_bits)).astype(jnp.uint8)
        exp_ref[...] = ((u >> man_bits) & exp_mask).astype(jnp.uint16)
        man_ref[...] = u & man_mask

    return kernel


def _merge_kernel(exp_bits: int, man_bits: int):
    def kernel(sign_ref, exp_ref, man_ref, u_ref):
        u_ref[...] = (
            (sign_ref[...].astype(jnp.uint32) << (exp_bits + man_bits))
            | (exp_ref[...].astype(jnp.uint32) << man_bits)
            | man_ref[...]
        )

    return kernel


def float_split_pallas(
    u: jax.Array, exp_bits: int, man_bits: int, *, interpret: bool
):
    n = u.shape[0]
    assert n % BLOCK == 0, "caller pads to BLOCK multiple"
    grid = (n // BLOCK,)
    spec = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _split_kernel(exp_bits, man_bits),
        grid=grid,
        in_specs=[spec],
        out_specs=(spec, spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((n,), jnp.uint8),
            jax.ShapeDtypeStruct((n,), jnp.uint16),
            jax.ShapeDtypeStruct((n,), jnp.uint32),
        ),
        interpret=interpret,
    )(u)


def float_merge_pallas(
    sign: jax.Array,
    exp: jax.Array,
    man: jax.Array,
    exp_bits: int,
    man_bits: int,
    *,
    interpret: bool,
):
    n = sign.shape[0]
    assert n % BLOCK == 0, "caller pads to BLOCK multiple"
    grid = (n // BLOCK,)
    spec = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _merge_kernel(exp_bits, man_bits),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
    )(sign, exp, man)
