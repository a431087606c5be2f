"""The harness's check, driven end to end on the CPU at a small size (the
look for a chip is skipped): a sound run is correct; the control (the data
one step below its stated precision) and each fault a compression cell can
have make ``correct`` false."""
import struct
import time
import zlib

import pytest

from bench.common import load_json, load_module
from bench.harness import run_cell

SEED = 2**31 + 77
# the cells as BENCHMARK.json states them, so that each path is checked here
# whichever cells the benchmark runs
CELLS = {
    "lineitem-sf1.bulk": {"name": "lineitem-sf1.bulk", "config": "tpch-lineitem-sf1",
                          "traffic": "bulk", "chips": 1},
    "olmoe-ckpt.save": {"name": "olmoe-ckpt.save", "config": "olmoe-1b-7b-ckpt",
                        "traffic": "bulk", "chips": 1},
    "lineitem-sf1.serve": {"name": "lineitem-sf1.serve", "config": "tpch-lineitem-sf1",
                           "traffic": "serve_open", "chips": 1},
}


def small_lineitem():
    cfg = load_json("configs", "tpch-lineitem-sf1")
    cfg.update(orders=2500, rows=10007, row_group_rows=4096)
    return cfg


def small_olmoe():
    cfg = load_json("configs", "olmoe-1b-7b-ckpt")
    cfg.update(hidden_size=128, intermediate_size=64, num_experts=16, chunk_bytes=1 << 14)
    return cfg


def small_serve():
    mix = load_json("traffic", "serve_open")
    mix.update(page_bytes=1 << 14, clients=4, grace_s=30.0, rate_per_s=20.0)
    return mix


def bulk(degrade=None, cell="lineitem-sf1.bulk", cfg=None):
    return run_cell(CELLS[cell], SEED, 0.5, False, time.perf_counter(),
                    cfg=cfg or small_lineitem(), degrade=degrade)


def serve(degrade=None):
    return run_cell(CELLS["lineitem-sf1.serve"], SEED, 1.0, False, time.perf_counter(),
                    cfg=small_lineitem(), mix=small_serve(), degrade=degrade)


def test_sound_bulk_run_is_correct_and_prints_its_checks():
    res = bulk()
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["bad_bytes"] == {"value": 0, "limit": 0}
    assert {"compress_mibps", "ratio", "setup_s"} <= set(res["metrics"])
    assert res["device"]["platform"] == "cpu"


def test_sound_serve_run_is_correct():
    res = serve()
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["unanswered"]["value"] == 0
    assert {"ratio", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("cell,cfg", [
    ("lineitem-sf1.bulk", small_lineitem),
    ("olmoe-ckpt.save", small_olmoe),
])
def test_control_one_step_below_the_precision_fails(cell, cfg):
    config = CELLS[cell]["config"]
    res = bulk(load_module("configs", config).control, cell=cell, cfg=cfg())
    assert res["correct"] is False
    assert res["checks"]["bad_bytes"]["value"] > 0


def _flip_payload(frame: bytes) -> bytes:
    body = bytearray(frame[:-4])
    body[len(body) * 3 // 4] ^= 0x01
    return bytes(body) + struct.pack("<I", zlib.crc32(bytes(body)) & 0xFFFFFFFF)


@pytest.fixture
def altered_answers(monkeypatch):
    """A byte of each frame altered where the wire writer produces it (the
    frame's crc made to match, so nothing but the content is wrong)."""
    from repro.core import wire

    real = wire.write_frame
    monkeypatch.setattr(wire, "write_frame", lambda *a, **k: _flip_payload(real(*a, **k)))


@pytest.fixture
def half_inputs(monkeypatch):
    """Half of each input left out: the program compresses the first half."""
    from repro.core import engine
    from repro.core.message import Stream

    real = engine.CompressorSession.compress

    def compress(self, inputs, **kw):
        s = inputs if isinstance(inputs, Stream) else inputs[0]
        half = Stream(s.data[: s.data.size // 2], s.stype, s.width)
        return real(self, half, **kw)

    monkeypatch.setattr(engine.CompressorSession, "compress", compress)


@pytest.fixture
def stale_answers(monkeypatch):
    """A call that returns its state unchanged: the previous call's frame."""
    from repro.core import engine

    real = engine.CompressorSession.compress
    last = {}

    def compress(self, inputs, **kw):
        frame = real(self, inputs, **kw)
        prev, last["frame"] = last.get("frame"), frame
        return prev if prev is not None else frame

    monkeypatch.setattr(engine.CompressorSession, "compress", compress)


@pytest.mark.parametrize("fault", ["altered_answers", "half_inputs", "stale_answers"])
def test_bulk_faults_make_the_run_incorrect(fault, request):
    request.getfixturevalue(fault)
    res = bulk()
    assert res["correct"] is False
    assert res["checks"]["bad_frames"]["value"] > 0


def test_serve_altered_answers_make_the_run_incorrect(altered_answers):
    res = serve()
    assert res["correct"] is False
    assert res["checks"]["bad_frames"]["value"] > 0


def test_serve_control_fails():
    res = serve(load_module("configs", "tpch-lineitem-sf1").control)
    assert res["correct"] is False
