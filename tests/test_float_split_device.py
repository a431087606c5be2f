"""float_split's device twin on 16-bit floats: bf16 (fmt 0) and f16 (fmt 1)
bit patterns split on the device give planes, headers and frames
byte-identical to the host encoder, which is the plain reference; f64
(fmt 3) stays on the host; ``float_split_info()`` counts the elements each
backend split, by fmt."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.codecs import floats
from repro.core import compress, decompress, numeric, pipeline
from repro.kernels import ops
from repro.kernels.float_split import BLOCK

rng = np.random.default_rng(15)

# +0, -0, smallest and largest denormals of each sign, +-inf, quiet and
# signalling NaNs with payloads, all ones
SPECIAL = {
    0: [0x0000, 0x8000, 0x0001, 0x007F, 0x8001, 0x807F, 0x7F80, 0xFF80,
        0x7FC0, 0x7F81, 0xFFC1, 0x7FFF, 0xFFFF],
    1: [0x0000, 0x8000, 0x0001, 0x03FF, 0x8001, 0x83FF, 0x7C00, 0xFC00,
        0x7E00, 0x7C01, 0xFE01, 0x7FFF, 0xFFFF],
}
SIZES = [0, 1, BLOCK - 1, BLOCK + 7, 3 * BLOCK + 5]


@pytest.fixture(autouse=True, scope="module")
def _restore_split_counts():
    """``float_split_info()`` counts for the whole process: leave it as this
    module found it, so a later test file in the same worker (which compares
    its fmt keys) sees no f16 device splits it did not make."""
    before = floats.float_split_info()
    yield
    with floats._split_lock:
        for backend, per in floats._split_elements.items():
            per.clear()
            per.update(before.get(backend, {}))


def patterns(fmt: int, n: int) -> np.ndarray:
    """n bit patterns of format ``fmt``: the special values first, then
    random normal-range values."""
    special = np.asarray(SPECIAL[fmt], dtype=np.uint16)
    if fmt == 0:
        rest = np.asarray(rng.normal(0, 0.02, n), dtype=jnp.bfloat16).view(np.uint16)
    else:
        rest = rng.normal(0, 0.02, n).astype(np.float16).view(np.uint16)
    return np.concatenate([special, rest])[:n]


def planes(enc, u: np.ndarray, fmt: int):
    outs, header = enc([numeric(u)], {"fmt": fmt})
    return [(s.stype, s.width, s.data.dtype, s.data.tobytes()) for s in outs], header


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("fmt", [0, 1])
def test_device_planes_and_frames_equal_the_host_encoder(fmt, n):
    u = patterns(fmt, n)
    assert floats._float_split_applies_device([numeric(u)], {"fmt": fmt})
    assert planes(floats._float_split_enc_device, u, fmt) == planes(
        floats._float_split_enc, u, fmt
    )
    plan = pipeline(("float_split", {"fmt": fmt}))
    before = floats.float_split_info()
    fd = compress(plan, numeric(u), backend="device")
    after = floats.float_split_info()
    assert fd == compress(plan, numeric(u), backend="host")
    assert decompress(fd)[0].content_bytes() == u.tobytes()
    split = after["device"].get(fmt, 0) - before["device"].get(fmt, 0)
    assert split == n  # the device route took it


@given(st.lists(st.integers(0, 0xFFFF), max_size=3000), st.sampled_from([0, 1]))
@settings(max_examples=20, deadline=None)
def test_device_planes_equal_the_host_encoder_on_any_bit_patterns(values, fmt):
    u = np.asarray(values, dtype=np.uint16)
    assert planes(floats._float_split_enc_device, u, fmt) == planes(
        floats._float_split_enc, u, fmt
    )


@pytest.mark.parametrize("fmt", [0, 1])
def test_pallas_kernel_splits_16_bit_patterns_as_the_host(fmt):
    """The Mosaic kernel (interpret mode here) on u16 input widened to its
    u32 lanes, against the host encoder's planes."""
    _, exp_bits, man_bits = floats.FORMATS[fmt]
    u = patterns(fmt, 2 * BLOCK + 3)
    sign, exp, man = ops.float_split(jnp.asarray(u), exp_bits, man_bits, use_pallas=True)
    (s_host, e_host, m_host), _ = floats._float_split_enc([numeric(u)], {"fmt": fmt})
    assert floats._pack_sign_bits(np.asarray(sign)).tobytes() == s_host.data.tobytes()
    assert np.asarray(exp).astype(floats._EXP_DTYPE[fmt]).tobytes() == e_host.data.tobytes()
    assert np.asarray(man).astype(floats._MAN_DTYPE[fmt]).tobytes() == m_host.data.tobytes()


@pytest.mark.parametrize("width,fmt,device", [
    (2, 0, True), (2, 1, True), (4, 2, True), (8, 3, False),
    (2, None, True), (4, None, True), (8, None, False),
    (2, 2, False), (4, 0, False),
])
def test_route_is_chosen_by_width_and_fmt(width, fmt, device):
    u = np.zeros(8, dtype={2: np.uint16, 4: np.uint32, 8: np.uint64}[width])
    params = {} if fmt is None else {"fmt": fmt}
    assert floats._float_split_applies_device([numeric(u)], params) is device


def test_float_split_info_counts_elements_by_backend_and_fmt():
    bf16 = patterns(0, 300)
    f32 = rng.normal(size=70).astype(np.float32).view(np.uint32)
    f64 = rng.normal(size=50).view(np.uint64)
    before = floats.float_split_info()
    compress(pipeline(("float_split", {"fmt": 0})), numeric(bf16), backend="device")
    compress(pipeline(("float_split", {"fmt": 0})), numeric(bf16), backend="host")
    compress(pipeline(("float_split", {"fmt": 2})), numeric(f32), backend="device")
    compress(pipeline(("float_split", {"fmt": 3})), numeric(f64), backend="device")
    after = floats.float_split_info()

    def grew(backend, fmt):
        return after[backend].get(fmt, 0) - before[backend].get(fmt, 0)

    assert (grew("device", 0), grew("host", 0)) == (300, 300)
    assert (grew("device", 2), grew("host", 2)) == (70, 0)
    assert (grew("device", 3), grew("host", 3)) == (0, 50)  # f64 stays on the host
    assert set(after) == {"device", "host"}
