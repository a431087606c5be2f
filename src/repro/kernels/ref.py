"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; the Pallas kernels must match them bit-exactly
(tests sweep shapes/dtypes and assert equality).  They are also the fallback
implementation on backends without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------- delta
def delta_encode(x: jax.Array) -> jax.Array:
    """out[0] = x[0]; out[i] = x[i] - x[i-1]  (wrapping, unsigned)."""
    return jnp.concatenate([x[:1], x[1:] - x[:-1]])


def delta_decode(d: jax.Array) -> jax.Array:
    return jnp.cumsum(d, dtype=d.dtype)


# --------------------------------------------------------------- byteshuffle
def byteshuffle_encode(x: jax.Array) -> jax.Array:
    """(n, w) uint8 records -> (w, n) byte planes (Blosc shuffle)."""
    return x.T


def byteshuffle_decode(p: jax.Array) -> jax.Array:
    return p.T


# ------------------------------------------------------------------- bitpack
def bitpack_encode(x: jax.Array, bits: int) -> jax.Array:
    """Pack uint32 values (< 2^bits) into uint32 words, LSB-first.

    bits must divide 32 (TPU variant restriction; the host codec supports
    arbitrary widths).  x.size must be a multiple of 32//bits.
    """
    per = 32 // bits
    v = x.reshape(-1, per).astype(jnp.uint32)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits).astype(jnp.uint32)
    return (v << shifts[None, :]).sum(axis=1, dtype=jnp.uint32)


def bitpack_decode(w: jax.Array, bits: int) -> jax.Array:
    per = 32 // bits
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits).astype(jnp.uint32)
    mask = jnp.uint32((1 << bits) - 1)
    return ((w[:, None] >> shifts[None, :]) & mask).reshape(-1)


# --------------------------------------------------------------- float_split
def float_split_encode(u: jax.Array, exp_bits: int, man_bits: int):
    """uint bit patterns -> (sign u8, exponent u8/u16, mantissa u32)."""
    u = u.astype(jnp.uint32)
    sign = (u >> (exp_bits + man_bits)).astype(jnp.uint8)
    exp_mask = jnp.uint32((1 << exp_bits) - 1)
    man_mask = jnp.uint32((1 << man_bits) - 1)
    exp = ((u >> man_bits) & exp_mask).astype(jnp.uint16)
    man = (u & man_mask).astype(jnp.uint32)
    return sign, exp, man


def float_split_decode(sign, exp, man, exp_bits: int, man_bits: int):
    u = (
        (sign.astype(jnp.uint32) << (exp_bits + man_bits))
        | (exp.astype(jnp.uint32) << man_bits)
        | man.astype(jnp.uint32)
    )
    return u


# ------------------------------------------------- fused delta+bitpack (v3)
def fused_delta_bitpack_encode(x: jax.Array, bits: int) -> jax.Array:
    """Beyond-paper fusion: one pass instead of two HBM round-trips."""
    return bitpack_encode(delta_encode(x) & jnp.uint32((1 << bits) - 1), bits)


def fused_delta_bitpack_decode(w: jax.Array, bits: int) -> jax.Array:
    # NOTE: only lossless when all deltas fit in `bits` (checked by caller)
    return delta_decode(bitpack_decode(w, bits))


# ------------------------------------------------------------- exact histogram
def histogram_exact(x: jax.Array) -> jax.Array:
    """256-bin histogram with integer accumulation — exact at any count
    (entropy-coder table construction needs exact counts)."""
    return jnp.bincount(x.astype(jnp.int32), length=256).astype(jnp.int32)


# ----------------------------------------------------------------- pack bits
def pack_bits(vals: jax.Array, offs: jax.Array, total_bytes: int):
    """Scatter pre-masked values to LSB-first packed bytes at bit offsets.

    The device twin of the host codecs' bit-matrix writer: symbol i
    contributes ``w = vals[i] << (offs[i] & 7)`` (<= 22 bits for 15-bit
    codes) to the four bytes starting at ``offs[i] >> 3``.  Every output
    *bit* has exactly one writer, so the per-byte scatter-**add** below can
    never carry — addition equals bitwise OR, and the packed bytes are
    bit-identical to the host writer's.  Values must be masked to their bit
    count already (zero-width entries carry ``vals == 0`` and add nothing).
    """
    base = offs >> 3
    w = vals.astype(jnp.uint32) << (offs & 7).astype(jnp.uint32)
    out = jnp.zeros((total_bytes + 4,), jnp.uint32)  # +4: last symbol's spill
    for t in range(4):
        out = out.at[base + t].add((w >> jnp.uint32(8 * t)) & jnp.uint32(0xFF))
    return out[:total_bytes].astype(jnp.uint8)


# ------------------------------------------------------------ huffman kernels
def huffman_map(x: jax.Array, codes: jax.Array, lens: jax.Array):
    """Per-symbol (canonical code, code length) table gathers."""
    xi = x.astype(jnp.int32)
    return jnp.take(codes.astype(jnp.uint32), xi), jnp.take(
        lens.astype(jnp.int32), xi
    )


def huffman_decode_lanes(
    buf: jax.Array, pos: jax.Array, lut_sym: jax.Array, lut_len: jax.Array, max_rem: int
):
    """Lane-parallel Huffman decode: one symbol per 32-bit window refill.

    ``buf`` is the bitstream padded >= 5 bytes past every cursor; ``pos``
    holds each lane's starting bit offset.  The host decoder drains three
    symbols per 64-bit refill; the device twin (no 64-bit lanes) refills per
    symbol — the *decoded symbols* are identical, which is all decode
    output is.  Returns (max_rem, n_lanes) u8; surplus rows of short lanes
    decode pad zeros and are trimmed by the caller.
    """
    sym = lut_sym.astype(jnp.int32)
    lnt = lut_len.astype(jnp.int32)
    n_lanes = pos.shape[0]
    out = jnp.zeros((max_rem, n_lanes), jnp.uint8)

    def step(i, carry):
        p, o = carry
        win = lane_refill(buf, p)
        low = (win & jnp.uint32(0x7FFF)).astype(jnp.int32)
        o = o.at[i].set(jnp.take(sym, low).astype(jnp.uint8))
        return p + jnp.take(lnt, low), o

    _, out = jax.lax.fori_loop(0, max_rem, step, (pos.astype(jnp.int32), out))
    return out


# ---------------------------------------------------------------- fse kernels
def fse_encode_lanes(
    lanesT: jax.Array,
    rem: jax.Array,
    nb0: jax.Array,
    thr: jax.Array,
    st0: jax.Array,
    delta: jax.Array,
    state_table: jax.Array,
):
    """tANS backward state walk, one vector lane per block (paper §II-A;
    state machine after the SCL FSE exemplar).

    ``lanesT`` is (max_rem, n_lanes) symbols; a lane of length r initializes
    its state at position r-1 and emits the low bits of its state for every
    earlier position.  The per-symbol helpers are gathered once over the
    whole plane; the loop carries only the lane states and steps through the
    2^table_log compact ``state_table`` (``_build_tables``), recording
    X = state + 2^table_log at every position.  Bit counts and values follow
    from the recorded plane after the loop.  Returns per-position (vals u32,
    nbits i32) planes plus the final per-lane states — the bit-I/O
    composition (offsets + packing) happens in ``pack_bits`` on the same
    device.  Arithmetic is all int32: states live in [0, 2*2^table_log).
    """
    max_rem, n_lanes = lanesT.shape
    total = state_table.shape[0]
    s = lanesT.astype(jnp.int32)
    nb0s = jnp.take(nb0.astype(jnp.int32), s)
    thrs = jnp.take(thr.astype(jnp.int32), s)
    deltas = jnp.take(delta.astype(jnp.int32), s)
    st0s = jnp.take(st0.astype(jnp.int32), s)
    pos = jnp.arange(max_rem, dtype=jnp.int32)
    rem = rem.astype(jnp.int32)
    stab = state_table.astype(jnp.int32)

    def step(state, row):
        i, nb0_i, thr_i, delta_i, st0_i = row
        X = state + total
        nb = nb0_i - (X < thr_i).astype(jnp.int32)
        nxt = jnp.take(stab, jnp.clip((X >> nb) + delta_i, 0, total - 1))
        state = jnp.where(
            rem > i + 1, nxt, jnp.where(rem == i + 1, st0_i, state)
        )
        return state, X

    state, X = jax.lax.scan(
        step,
        jnp.zeros(n_lanes, jnp.int32),
        (pos, nb0s, thrs, deltas, st0s),
        reverse=True,
    )
    emit = rem[None, :] > pos[:, None] + 1
    nbs = jnp.where(emit, nb0s - (X < thrs).astype(jnp.int32), 0)
    vals = X.astype(jnp.uint32) & (
        (jnp.uint32(1) << nbs.astype(jnp.uint32)) - jnp.uint32(1)
    )
    return vals, nbs, state


def fse_decode_lanes(
    flat: jax.Array,
    lane_base: jax.Array,
    bitlen: jax.Array,
    state0: jax.Array,
    dec_sym: jax.Array,
    dec_nb: jax.Array,
    dec_base: jax.Array,
    max_rem: int,
):
    """Lane-parallel tANS decode: forward symbol order, backward bit reads.

    ``flat`` is the concatenation of per-lane padded buffers (``lane_base``
    byte offsets); each lane's cursor starts at its bitstream length and
    walks backward.  Exhausted lanes read pad zeros and walk garbage states
    that stay in-table (base + bits < 2^table_log by construction); their
    surplus rows are trimmed by the caller.
    """
    sym = dec_sym.astype(jnp.int32)
    nbt = dec_nb.astype(jnp.int32)
    bst = dec_base.astype(jnp.int32)
    n_lanes = bitlen.shape[0]
    out = jnp.zeros((max_rem, n_lanes), jnp.uint8)

    def step(i, carry):
        state, cursor, o = carry
        o = o.at[i].set(jnp.take(sym, state).astype(jnp.uint8))
        nb = jnp.take(nbt, state)
        base = jnp.take(bst, state)
        cursor = cursor - nb
        byte0 = jnp.maximum(cursor >> 3, 0)
        win = lane_refill(flat, (lane_base + byte0) * 8 + (cursor & 7))
        bits = win & ((jnp.uint32(1) << nb.astype(jnp.uint32)) - jnp.uint32(1))
        return base + bits.astype(jnp.int32), cursor, o

    _, _, out = jax.lax.fori_loop(
        0,
        max_rem,
        step,
        (state0.astype(jnp.int32), bitlen.astype(jnp.int32), out),
    )
    return out


# --------------------------------------------------------------- lane refill
def lane_refill(buf: jax.Array, bitpos: jax.Array) -> jax.Array:
    """Entropy-lane window refill: next 32 bits at each lane's bit cursor.

    ``buf`` is the (padded) bitstream as uint8; the result is the LSB-first
    32-bit window a lane decoder consumes next.  Device twin of the numpy
    sliding-window gather in ``repro.codecs.entropy`` (32-bit because TPU
    lanes have no native 64-bit ints).
    """
    w32 = buf.astype(jnp.uint32)
    byte0 = bitpos.astype(jnp.int32) >> 3
    r = (bitpos.astype(jnp.int32) & 7).astype(jnp.uint32)
    b = [jnp.take(w32, byte0 + k) for k in range(5)]
    lo = b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
    return (lo >> r) | ((b[4] << 1) << (jnp.uint32(31) - r))
