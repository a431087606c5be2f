"""OpenZL-compressed checkpointing: roundtrip, atomicity, keep-K, resume,
elastic restore, corruption detection (paper §VIII checkpoint use case)."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.checkpoint import (
    CheckpointManager,
    compress_leaf,
    decompress_leaf,
    latest_step,
    restore_checkpoint,
    restore_tree,
    save_checkpoint,
)

rng = np.random.default_rng(0)


def tree_eq(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(fa, fb))


@pytest.fixture
def tree():
    return {
        "params": {
            "w": rng.normal(size=(64, 32)).astype(np.float32),
            "emb": rng.normal(size=(100, 16)).astype(np.float32),
            "steps": np.arange(50, dtype=np.int32),
        },
        "opt": {"m": rng.normal(size=(64, 32)).astype(np.float32), "count": np.int32(7)},
    }


def test_leaf_roundtrip_dtypes():
    for arr in [
        rng.normal(size=1000).astype(np.float32),
        rng.normal(size=1000).astype(np.float64),
        rng.integers(0, 1 << 30, 1000).astype(np.int64),
        rng.integers(0, 255, 1000).astype(np.uint8),
        (rng.random(1000) > 0.5),
        jnp.asarray(rng.normal(size=512), jnp.bfloat16),
    ]:
        arr = np.asarray(arr)
        frame = compress_leaf(arr)
        back = decompress_leaf(frame, arr.shape, arr.dtype)
        assert back.dtype == arr.dtype
        assert np.array_equal(back, arr)


def test_save_restore_roundtrip(tmp_path, tree):
    m = save_checkpoint(tmp_path, 10, tree)
    assert m["ratio"] > 1.0  # float-split graphs beat raw floats
    restored, manifest = restore_tree(tmp_path, tree, 10)
    assert tree_eq(tree, restored)
    assert manifest["step"] == 10


def test_bf16_embedding_compression_beats_raw(tmp_path):
    """Paper §VIII: bf16 embeddings compress ~30%; random normals compress
    less but MUST still beat raw (exponent plane is low entropy)."""
    emb = jnp.asarray(rng.normal(size=(1 << 14,)).astype(np.float32), jnp.bfloat16)
    tree = {"emb": emb}
    m = save_checkpoint(tmp_path, 1, tree)
    assert m["compressed_bytes"] < m["raw_bytes"] * 0.95
    restored, _ = restore_tree(tmp_path, tree, 1)
    assert np.array_equal(np.asarray(restored["emb"]), np.asarray(emb))


def test_atomicity_no_tmp_visible(tmp_path, tree):
    save_checkpoint(tmp_path, 5, tree)
    assert not list(tmp_path.glob("*.tmp"))
    assert latest_step(tmp_path) == 5


def test_partial_checkpoint_ignored(tmp_path, tree):
    save_checkpoint(tmp_path, 5, tree)
    save_checkpoint(tmp_path, 10, tree)
    # corrupt step 10: delete a leaf file
    victim = next((tmp_path / "step_0000000010").glob("leaf_*.ozl"))
    victim.unlink()
    assert latest_step(tmp_path) == 5  # falls back to last valid


def test_crc_detects_bitrot(tmp_path, tree):
    save_checkpoint(tmp_path, 5, tree)
    victim = next((tmp_path / "step_0000000005").glob("leaf_*.ozl"))
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0x01
    victim.write_bytes(bytes(blob))
    with pytest.raises((IOError, ValueError)):
        restore_checkpoint(tmp_path, 5)


def test_manager_keep_k_and_resume(tmp_path, tree):
    mgr = CheckpointManager(tmp_path, save_interval=10, keep=2)
    for step in (10, 20, 30):
        mgr.save(step, tree)
    mgr.wait()
    steps = sorted(d.name for d in tmp_path.iterdir() if d.name.startswith("step_"))
    assert len(steps) == 2  # keep-K enforced
    out = mgr.restore_or_none(tree)
    assert out is not None and out[0] == 30


def test_async_save(tmp_path, tree):
    mgr = CheckpointManager(tmp_path, async_save=True)
    mgr.save(7, tree)
    mgr.wait()
    assert mgr.latest_step() == 7


def test_elastic_restore_resharding(tmp_path, tree):
    """Leaves are stored unsharded: restore works onto any device layout."""
    save_checkpoint(tmp_path, 3, tree)
    shardings = jax.tree.map(
        lambda x: jax.sharding.SingleDeviceSharding(jax.devices()[0]), tree
    )
    restored, _ = restore_tree(tmp_path, tree, 3, shardings=shardings)
    assert tree_eq(tree, restored)
    assert all(
        isinstance(x, jax.Array) for x in jax.tree.leaves(restored)
    )


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_compress_leaf_on_a_tpu_gives_the_benchmark_session_frame(monkeypatch, tmp_path, dtype):
    """Where the one device decision answers "TPU", a leaf goes through a
    device-backend session in 4 MiB chunks: the same frame as
    ``CompressorSession(<dtype profile>, backend="device")`` chunked at
    4 MiB, which is the checkpoint cell's call; restore reads it back."""
    from repro.codecs import bfloat16_profile, float32_profile
    from repro.core import CompressorSession, numeric
    from repro.distributed import checkpoint

    n = (5 << 20) // np.dtype(jnp.bfloat16 if dtype == "bfloat16" else np.float32).itemsize
    leaf = np.asarray(jnp.asarray(0.02 * rng.normal(size=n), dtype))  # two chunks
    bits = leaf.view(np.uint16 if dtype == "bfloat16" else np.uint32)
    profile = bfloat16_profile() if dtype == "bfloat16" else float32_profile()
    cell = CompressorSession(profile, backend="device")
    try:
        want = cell.compress(numeric(bits), chunk_bytes=4 << 20)
    finally:
        cell.close()
    checkpoint.close_codec_sessions()
    monkeypatch.setattr(checkpoint, "on_tpu", lambda: True)
    try:
        assert compress_leaf(leaf) == want
        (sess,) = checkpoint._ENC_SESSIONS.values()
        assert sess.backend == "device" and sess.stats["chunks"] == 2
        assert "float_split" not in sess.stats["nodes"].get("host", {})
        save_checkpoint(tmp_path, 1, {"w": leaf})
        restored, _ = restore_checkpoint(tmp_path, 1)
    finally:
        checkpoint.close_codec_sessions()
    assert restored["w"].dtype == leaf.dtype and np.array_equal(restored["w"], leaf)
