"""Compile every data-path kernel for a v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``get_topology_desc``).  Each case lowers the jit'd
``repro.kernels.ops`` wrapper the device backend calls, at 4 Mi elements,
with the one device decision (``ops.on_tpu``) answering as it does on a TPU,
and compiles it for one v5e chip: what Mosaic or XLA would refuse on the
chip fails here.  Pallas cases must contain a Mosaic kernel
(``tpu_custom_call``), which interpret mode never emits.  Nothing runs, so
these say nothing about results or times; tests/test_kernels.py checks the
results in interpret mode.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 4 << 20  # elements per stream: a 16 MiB u32 / 4 MiB u8 chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip: keep these out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no trace made under the CPU answer may be reused
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    jax.clear_caches()  # drop traces made under the TPU answer below


@pytest.fixture
def tpu_ops(monkeypatch):
    """``repro.kernels.ops`` with the device decision answering "TPU"."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops._pallas(None) and not ops._interpret()
    return ops


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


U32 = ((N,), jnp.uint32)
U16 = ((N,), jnp.uint16)
U8 = ((N,), jnp.uint8)
TABLE_U32 = ((256,), jnp.uint32)
TABLE_I32 = ((256,), jnp.int32)


def _compile_fse_encode(one_chip, ops, case):
    """The tANS encode walk of one 4 MiB u8 chunk's 1024-symbol lane blocks
    (FSE_BLOCK_LOG = 10) on the compact 2^table_log state table; the
    ``lanes512`` case is a 512 Ki-symbol plane (a bf16 attention leaf)."""
    lanes = (N >> 13) if case.endswith("lanes512") else (N >> 10)
    table_log = 12 if case.endswith("tl12") else 11
    return _compile(
        one_chip,
        ops.fse_encode,
        ((1024, lanes), jnp.uint8),
        ((lanes,), jnp.int32),
        TABLE_I32,
        TABLE_I32,
        TABLE_I32,
        TABLE_I32,
        ((1 << table_log,), jnp.int32),
    )


# ------------------------------------------------------------ Pallas kernels
@pytest.mark.parametrize(
    "case",
    ["delta_encode", "float_split", "float_split_bf16", "float_split_f16", "huffman_map"]
    + [f"bitpack{b}" for b in (1, 2, 4, 8, 16)]
    + [f"fused_delta_bitpack{b}" for b in (1, 2, 4, 8, 16)]
    + [f"byteshuffle{w}" for w in (1, 4, 8)]
    + ["fse_encode", "fse_encode_tl12", "fse_encode_lanes512"],
)
def test_pallas_kernel_compiles_for_v5e(one_chip, tpu_ops, case):
    ops = tpu_ops
    if case == "delta_encode":
        text = _compile(one_chip, ops.delta_encode, U32)
    elif case == "float_split":
        text = _compile(one_chip, lambda u: ops.float_split(u, 8, 23), U32)
    elif case == "float_split_bf16":  # bf16 bit patterns, widened in the wrapper
        text = _compile(one_chip, lambda u: ops.float_split(u, 8, 7), U16)
    elif case == "float_split_f16":
        text = _compile(one_chip, lambda u: ops.float_split(u, 5, 10), U16)
    elif case == "huffman_map":
        text = _compile(one_chip, ops.huffman_map, U8, TABLE_U32, TABLE_I32)
    elif case.startswith("bitpack"):
        bits = int(case[len("bitpack"):])
        text = _compile(one_chip, lambda x: ops.bitpack(x, bits), U32)
    elif case.startswith("fse_encode"):
        text = _compile_fse_encode(one_chip, ops, case)
    elif case.startswith("fused_delta_bitpack"):
        bits = int(case[len("fused_delta_bitpack"):])
        text = _compile(one_chip, lambda x: ops.fused_delta_bitpack(x, bits), U32)
    else:
        w = int(case[len("byteshuffle"):])
        text = _compile(one_chip, ops.byteshuffle, ((N, w), jnp.uint8))
    assert "tpu_custom_call" in text, f"{case}: no Mosaic kernel in the program"


# ------------------------------------------------------------ plain XLA glue
@pytest.mark.parametrize("case", ["histogram_exact", "pack_bits"])
def test_xla_glue_compiles_for_v5e(one_chip, tpu_ops, case):
    ops = tpu_ops
    if case == "histogram_exact":
        _compile(one_chip, ops.histogram_exact, U8)
    else:
        # 15-bit codes at most: a bucketed 8 MiB capacity covers 4 Mi symbols
        _compile(
            one_chip,
            lambda v, o: ops.pack_bits(v, o, 8 << 20),
            U32,
            ((N,), jnp.int32),
        )
