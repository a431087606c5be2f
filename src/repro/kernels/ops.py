"""Jit'd public wrappers around the Pallas kernels.

Handles capacity padding (XLA static shapes — DESIGN.md §2.1), kernel
selection and the lossless-precondition checks for the fused kernel.

Kernel selection is ``repro.device.on_tpu()``, decided here and nowhere
else: ``use_pallas=None`` (what the device backend passes) means the Pallas
kernel compiled by Mosaic on a TPU and the jnp oracle in ref.py elsewhere.
An explicit ``use_pallas=True`` off the TPU runs the kernel in interpret
mode (the kernel tests); on a TPU a kernel never runs in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.device import on_tpu

from . import ref
from .bitpack import BLOCK_VALS, BLOCK_WORDS, bitpack_pallas, bitunpack_pallas
from .byteshuffle import BLOCK as SHUF_BLOCK, byteshuffle_pallas, byteunshuffle_pallas
from .delta import BLOCK as DELTA_BLOCK, delta_decode_pallas, delta_encode_pallas
from .float_split import BLOCK as FS_BLOCK, float_merge_pallas, float_split_pallas
from .fused_delta_bitpack import (
    fused_delta_bitpack_decode_pallas,
    fused_delta_bitpack_pallas,
)

Flag = Optional[bool]  # use_pallas: None = the device decision


def _pallas(use_pallas: Flag) -> bool:
    return on_tpu() if use_pallas is None else use_pallas


def _interpret() -> bool:
    return not on_tpu()


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])


# --------------------------------------------------------------------- delta
@functools.partial(jax.jit, static_argnames=("use_pallas",))
def delta_encode(x: jax.Array, *, use_pallas: Flag = None) -> jax.Array:
    x = x.astype(jnp.uint32)
    if x.shape[0] == 0:
        return x
    if not _pallas(use_pallas):
        return ref.delta_encode(x)
    n = x.shape[0]
    out = delta_encode_pallas(_pad_to(x, DELTA_BLOCK), interpret=_interpret())
    return out[:n]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def delta_decode(d: jax.Array, *, use_pallas: Flag = None) -> jax.Array:
    d = d.astype(jnp.uint32)
    if d.shape[0] == 0:
        return d
    if not _pallas(use_pallas):
        return ref.delta_decode(d)
    n = d.shape[0]
    out = delta_decode_pallas(_pad_to(d, DELTA_BLOCK), interpret=_interpret())
    return out[:n]


# --------------------------------------------------------------- byteshuffle
@functools.partial(jax.jit, static_argnames=("use_pallas",))
def byteshuffle(x: jax.Array, *, use_pallas: Flag = None) -> jax.Array:
    """(n, w) uint8 -> (w, n)."""
    if x.shape[0] == 0:
        return x.T
    if not _pallas(use_pallas):
        return ref.byteshuffle_encode(x)
    n = x.shape[0]
    out = byteshuffle_pallas(_pad_to(x, SHUF_BLOCK), interpret=_interpret())
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def byteunshuffle(p: jax.Array, *, use_pallas: Flag = None) -> jax.Array:
    """(w, n) uint8 -> (n, w)."""
    if p.shape[1] == 0:
        return p.T
    if not _pallas(use_pallas):
        return ref.byteshuffle_decode(p)
    w, n = p.shape
    pad = (-n) % SHUF_BLOCK
    if pad:
        p = jnp.concatenate([p, jnp.zeros((w, pad), p.dtype)], axis=1)
    out = byteunshuffle_pallas(p, interpret=_interpret())
    return out[:n]


# ------------------------------------------------------------------- bitpack
@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def bitpack(x: jax.Array, bits: int, *, use_pallas: Flag = None) -> jax.Array:
    """Returns packed words for ceil(n/per) values; caller tracks n."""
    x = x.astype(jnp.uint32)
    per = 32 // bits
    n = x.shape[0]
    n_words = -(-n // per)
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    if not _pallas(use_pallas):
        return ref.bitpack_encode(_pad_to(x, per), bits)[:n_words]
    out = bitpack_pallas(_pad_to(x, BLOCK_VALS), bits, interpret=_interpret())
    return out[:n_words]


@functools.partial(jax.jit, static_argnames=("bits", "n", "use_pallas"))
def bitunpack(
    w: jax.Array, bits: int, n: int, *, use_pallas: Flag = None
) -> jax.Array:
    if w.shape[0] == 0:
        return jnp.zeros((n,), jnp.uint32)
    if not _pallas(use_pallas):
        return ref.bitpack_decode(w, bits)[:n]
    out = bitunpack_pallas(_pad_to(w, BLOCK_WORDS), bits, interpret=_interpret())
    return out[:n]


# --------------------------------------------------------------- float_split
@functools.partial(jax.jit, static_argnames=("exp_bits", "man_bits", "use_pallas"))
def float_split(
    u: jax.Array, exp_bits: int, man_bits: int, *, use_pallas: Flag = None
):
    u = u.astype(jnp.uint32)
    if u.shape[0] == 0:
        return ref.float_split_encode(u, exp_bits, man_bits)
    if not _pallas(use_pallas):
        return ref.float_split_encode(u, exp_bits, man_bits)
    n = u.shape[0]
    sign, exp, man = float_split_pallas(
        _pad_to(u, FS_BLOCK), exp_bits, man_bits, interpret=_interpret()
    )
    return sign[:n], exp[:n], man[:n]


@functools.partial(jax.jit, static_argnames=("exp_bits", "man_bits", "use_pallas"))
def float_merge(
    sign, exp, man, exp_bits: int, man_bits: int, *, use_pallas: Flag = None
):
    if sign.shape[0] == 0:
        return ref.float_split_decode(sign, exp, man, exp_bits, man_bits)
    if not _pallas(use_pallas):
        return ref.float_split_decode(sign, exp, man, exp_bits, man_bits)
    n = sign.shape[0]
    out = float_merge_pallas(
        _pad_to(sign, FS_BLOCK),
        _pad_to(exp, FS_BLOCK),
        _pad_to(man, FS_BLOCK),
        exp_bits,
        man_bits,
        interpret=_interpret(),
    )
    return out[:n]


# ------------------------------------------------- fused delta+bitpack (K1)
def fused_delta_bitpack_fits(x: jax.Array, bits: int) -> jax.Array:
    """Lossless precondition: every wrapped delta fits in `bits`."""
    d = ref.delta_encode(x.astype(jnp.uint32))
    return jnp.all(d < jnp.uint32(1 << bits))


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def fused_delta_bitpack(x: jax.Array, bits: int, *, use_pallas: Flag = None):
    x = x.astype(jnp.uint32)
    per = 32 // bits
    n = x.shape[0]
    n_words = -(-n // per)
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    if not _pallas(use_pallas):
        return ref.fused_delta_bitpack_encode(_pad_to(x, per), bits)[:n_words]
    # pad by REPEATING the last value so padded deltas are 0 (still fit)
    pad = (-n) % BLOCK_VALS
    if pad and n:
        x = jnp.concatenate([x, jnp.broadcast_to(x[-1], (pad,))])
    elif pad:
        x = jnp.zeros(pad, jnp.uint32)
    out = fused_delta_bitpack_pallas(x, bits, interpret=_interpret())
    return out[:n_words]


@functools.partial(jax.jit, static_argnames=("bits", "n", "use_pallas"))
def fused_delta_bitpack_decode(
    w: jax.Array, bits: int, n: int, *, use_pallas: Flag = None
):
    if w.shape[0] == 0:
        return jnp.zeros((n,), jnp.uint32)
    if not _pallas(use_pallas):
        return ref.fused_delta_bitpack_decode(w, bits)[:n]
    out = fused_delta_bitpack_decode_pallas(
        _pad_to(w, BLOCK_WORDS), bits, interpret=_interpret()
    )
    return out[:n]


# ---------------------------------------------------------- entropy: huffman
@functools.partial(jax.jit, static_argnames=())
def histogram_exact(x: jax.Array) -> jax.Array:
    """256-bin counts with integer accumulation — exact at any stream size.

    Entropy-coder table construction needs exact counts, so the device twins
    use this (scatter-add, plain XLA on every backend)."""
    return ref.histogram_exact(x.astype(jnp.uint8))


@functools.partial(jax.jit, static_argnames=("total_bytes",))
def pack_bits(vals: jax.Array, offs: jax.Array, total_bytes: int) -> jax.Array:
    """Scatter-add bit packer (see ref.pack_bits): bit-identical to the host
    bit-matrix writer.  ``total_bytes`` is static — callers pass a bucketed
    capacity and trim, so content-dependent sizes don't recompile."""
    return ref.pack_bits(vals.astype(jnp.uint32), offs.astype(jnp.int32), total_bytes)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def huffman_map(
    x: jax.Array, codes: jax.Array, lens: jax.Array, *, use_pallas: Flag = None
):
    """Symbols -> (canonical code u32, nbits i32, exclusive bit offs i32[n+1]).

    ``offs[-1]`` is the total bit count; the cumsum stays int32, so callers
    gate stream size at <= 2^27 symbols (15 bits/code max)."""
    from .huffman import MAP_BLOCK, huffman_map_pallas

    x = x.astype(jnp.uint8)
    codes = codes.astype(jnp.uint32)
    lens = lens.astype(jnp.int32)
    n = x.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.uint32)
        return z, z.astype(jnp.int32), jnp.zeros((1,), jnp.int32)
    if _pallas(use_pallas):
        code, nb = huffman_map_pallas(
            _pad_to(x, MAP_BLOCK), codes, lens, interpret=_interpret()
        )
        code, nb = code[:n], nb[:n]
    else:
        code, nb = ref.huffman_map(x, codes, lens)
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(nb, dtype=jnp.int32)]
    )
    return code, nb, offs


@functools.partial(jax.jit, static_argnames=("max_rem", "use_pallas"))
def huffman_decode(
    buf: jax.Array,
    pos: jax.Array,
    lut_sym: jax.Array,
    lut_len: jax.Array,
    max_rem: int,
    *,
    use_pallas: Flag = None,
):
    """Lane-parallel Huffman decode -> (max_rem, n_lanes) u8 symbols.

    ``buf`` must be padded so every cursor has the host decoder's overrun
    room; surplus rows of short lanes are pad garbage the caller trims."""
    from .huffman import LANE_BLOCK, huffman_decode_pallas

    buf = buf.astype(jnp.uint8)
    n = pos.shape[0]
    if n == 0 or max_rem == 0:
        return jnp.zeros((max_rem, n), jnp.uint8)
    if not _pallas(use_pallas):
        return ref.huffman_decode_lanes(buf, pos, lut_sym, lut_len, max_rem)
    out = huffman_decode_pallas(
        buf,
        _pad_to(pos.astype(jnp.int32), LANE_BLOCK),
        lut_sym.astype(jnp.int32),
        lut_len.astype(jnp.int32),
        max_rem,
        interpret=_interpret(),
    )
    return out[:, :n]


# -------------------------------------------------------------- entropy: fse
@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fse_encode(
    lanesT: jax.Array,
    rem: jax.Array,
    nb0: jax.Array,
    thr: jax.Array,
    st0: jax.Array,
    delta: jax.Array,
    state_table: jax.Array,
    *,
    use_pallas: Flag = None,
):
    """tANS backward walk on the compact state table + wire-layout offsets.

    ``lanesT`` is (1 << FSE_BLOCK_LOG, n_lanes) symbols, ``rem`` each lane's
    length; nb0, thr, st0 and delta are the per-symbol helpers and
    ``state_table`` the 2^table_log compact table of ``_build_tables``.
    The walk carries only the lane states and takes one gather from the
    compact table a step (kernels/fse.py; ``ref.fse_encode_lanes`` is its
    jnp oracle).  Returns (vals u32 planes, global bit offsets i32 planes,
    final states, per-lane bit lengths, lane byte offsets i32[n+1]).  The
    offsets place every emission directly into the *concatenated* per-lane
    bitstream layout the host encoder produces, so one ``pack_bits`` call
    yields the final wire bytes."""
    from .fse import ENC_LANES, LANES, fse_encode_pallas

    rem = rem.astype(jnp.int32)
    if not _pallas(use_pallas):
        vals, nbs, state = ref.fse_encode_lanes(
            lanesT, rem, nb0, thr, st0, delta, state_table
        )
    else:
        max_rem, n = lanesT.shape
        pad = (-n) % ENC_LANES
        sym = jnp.pad(lanesT.astype(jnp.int32), ((0, 0), (0, pad)))
        # a lane of length r starts at position r-1 in state st0[sym]
        start = sym[jnp.maximum(rem - 1, 0), jnp.arange(n)]
        init = _pad_to(jnp.take(st0.astype(jnp.int32), start), ENC_LANES)
        nbthr = nb0.astype(jnp.int32) | (thr.astype(jnp.int32) << 5)
        lanes3 = lambda a: a.reshape(a.shape[:-1] + (-1, LANES))
        vals, nbs, state = fse_encode_pallas(
            lanes3(sym),
            lanes3(_pad_to(rem, ENC_LANES)),
            lanes3(init),
            nbthr,
            delta.astype(jnp.int32),
            state_table.astype(jnp.int32),
            interpret=_interpret(),
        )
        vals = vals.reshape(max_rem, -1)[:, :n].astype(jnp.uint32)
        nbs = nbs.reshape(max_rem, -1)[:, :n]
        state = state.reshape(-1)[:n]
    bitpos = jnp.sum(nbs, axis=0, dtype=jnp.int32)
    # emission order is decreasing position i, so the offset of emission i
    # within its lane is the suffix sum of later positions' bit counts
    suffix = jnp.cumsum(nbs[::-1], axis=0, dtype=jnp.int32)[::-1]
    intra = suffix - nbs
    nbytes = (bitpos + 7) >> 3
    byte_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(nbytes, dtype=jnp.int32)]
    )
    goffs = byte_off[None, :-1] * 8 + intra
    return vals, goffs, state, bitpos, byte_off


@functools.partial(jax.jit, static_argnames=("max_rem", "use_pallas"))
def fse_decode(
    flat: jax.Array,
    lane_base: jax.Array,
    bitlen: jax.Array,
    state0: jax.Array,
    dec_sym: jax.Array,
    dec_nb: jax.Array,
    dec_base: jax.Array,
    max_rem: int,
    *,
    use_pallas: Flag = None,
):
    """Lane-parallel tANS decode -> (max_rem, n_lanes) u8 symbols."""
    from .fse import LANE_BLOCK, fse_decode_pallas

    flat = flat.astype(jnp.uint8)
    n = bitlen.shape[0]
    if n == 0 or max_rem == 0:
        return jnp.zeros((max_rem, n), jnp.uint8)
    if not _pallas(use_pallas):
        return ref.fse_decode_lanes(
            flat, lane_base, bitlen, state0, dec_sym, dec_nb, dec_base, max_rem
        )
    out = fse_decode_pallas(
        flat,
        _pad_to(lane_base.astype(jnp.int32), LANE_BLOCK),
        _pad_to(bitlen.astype(jnp.int32), LANE_BLOCK),
        _pad_to(state0.astype(jnp.int32), LANE_BLOCK),
        dec_sym.astype(jnp.int32),
        dec_nb.astype(jnp.int32),
        dec_base.astype(jnp.int32),
        max_rem,
        interpret=_interpret(),
    )
    return out[:, :n]


# --------------------------------------------------------------- lane refill
@functools.partial(jax.jit, static_argnames=("use_pallas",))
def lane_refill(buf: jax.Array, bitpos: jax.Array, *, use_pallas: Flag = None):
    """Entropy-lane window refill: next 32 bits per lane bit-cursor, u32.

    The device-side building block of the entropy decoders' gather refill
    (``repro.codecs.entropy`` lane-refill scheme).  ``buf`` must be padded
    so every cursor has >= 5 readable bytes; lanes are padded to the kernel
    block internally.  Bit-exact with the numpy host path (tests).
    """
    from .lane_refill import BLOCK as REFILL_BLOCK, lane_refill_pallas

    n = bitpos.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    buf = buf.astype(jnp.uint8)
    if not _pallas(use_pallas):
        return ref.lane_refill(buf, bitpos)
    pos = _pad_to(bitpos.astype(jnp.int32), REFILL_BLOCK)
    out = lane_refill_pallas(buf, pos, interpret=_interpret())
    return out[:n]
