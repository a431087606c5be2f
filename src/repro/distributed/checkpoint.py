"""Distributed checkpointing with OpenZL compression (paper §VIII "PyTorch
model checkpoints" / "Embedding storage").

Every pytree leaf is compressed with the float-split graphs (f32/bf16/f64) or
the numeric auto-profile — the exact technique the paper deploys at Meta
(~17% on fp32 checkpoints, ~30% on bf16 embeddings).  Frames are
self-describing, so restore needs no compressor config (universal decoder).

Fault-tolerance contract:
  * atomic: write to step_<n>.tmp, fsync, rename — a crash never leaves a
    half checkpoint visible;
  * restartable: CheckpointManager.restore_latest() picks the newest valid
    manifest (corrupt/partial steps are skipped with a warning);
  * elastic: leaves are stored as FULL (unsharded) arrays + the manifest
    records shapes/dtypes, so restore can re-shard onto ANY mesh
    (restore_for_shardings);
  * async: save() can overlap the next train step (background thread).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.codecs import (
    bfloat16_profile,
    float32_profile,
    float64_profile,
    numeric_profile,
)
from repro.core import CompressorSession, DecompressorSession, numeric
from repro.core.graph import Plan, pipeline as plan_pipeline
from repro.device import on_tpu
from repro.reliability.faults import crash_point

MANIFEST = "manifest.json"
# leaves are compressed in chunks of this many bytes (the CLI's default)
CHUNK_BYTES = 4 << 20

# ------------------------------------------------- long-lived codec sessions
# One CompressorSession per distinct leaf plan and one shared
# DecompressorSession per worker process: thousands of checkpoint leaves reuse
# the same resolve cache, coder-table scratch, and thread pool instead of
# paying session construction per leaf.  Sessions are thread-safe, so the
# async-save background thread shares them with the restore path.  On a TPU
# the encode sessions run on the device backend; elsewhere on the host.
_SESSION_LOCK = threading.Lock()
_ENC_SESSIONS: Dict[Plan, CompressorSession] = {}
_DEC_SESSION: list = []  # 0 or 1 DecompressorSession


def _enc_session(plan: Plan) -> CompressorSession:
    with _SESSION_LOCK:
        sess = _ENC_SESSIONS.get(plan)
        if sess is None:
            backend = "device" if on_tpu() else "host"
            sess = _ENC_SESSIONS[plan] = CompressorSession(
                plan, backend=backend, chunk_bytes=CHUNK_BYTES
            )
        return sess


def _dec_session() -> DecompressorSession:
    with _SESSION_LOCK:
        if not _DEC_SESSION:
            _DEC_SESSION.append(DecompressorSession())
        return _DEC_SESSION[0]


def codec_session_stats() -> dict:
    """Aggregate encode/decode session counters (for serving diagnostics)."""
    with _SESSION_LOCK:
        enc = [s.stats for s in _ENC_SESSIONS.values()]
        dec = _DEC_SESSION[0].stats if _DEC_SESSION else {}
    agg = {"enc_plans": len(enc)}
    for k in ("calls", "bytes_in", "bytes_out"):
        agg[f"enc_{k}"] = sum(s[k] for s in enc)
        agg[f"dec_{k}"] = int(dec.get(k, 0))
    return agg


def close_codec_sessions() -> None:
    """Release session thread pools (tests / worker shutdown)."""
    with _SESSION_LOCK:
        sessions = list(_ENC_SESSIONS.values()) + list(_DEC_SESSION)
        _ENC_SESSIONS.clear()
        _DEC_SESSION.clear()
    for s in sessions:
        s.close()


def _leaf_key(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


# Trained-plan overrides (the `repro train` -> deploy loop, paper §VI-C):
# a plan registered for a dtype name ("float32", ...) — or "*" for all
# dtypes — replaces the shipped profile for checkpoint leaves.  Restore is
# unaffected: frames are self-describing, the universal decoder reads both.
_PLAN_OVERRIDES: Dict[str, Plan] = {}


def set_checkpoint_plan(dtype_name: str, plan: Optional[Plan]) -> None:
    """Route checkpoint leaves of ``dtype_name`` (or ``"*"``) through
    ``plan`` — typically a deserialized trained ``.ozp``.  ``None`` clears
    the override."""
    with _SESSION_LOCK:
        if plan is None:
            _PLAN_OVERRIDES.pop(dtype_name, None)
        else:
            _PLAN_OVERRIDES[dtype_name] = plan.validate()


def _plan_for_dtype(dtype) -> Tuple[Plan, bool]:
    """-> (plan, is_trained_override)."""
    name = str(dtype)
    with _SESSION_LOCK:
        override = _PLAN_OVERRIDES.get(name) or _PLAN_OVERRIDES.get("*")
    if override is not None:
        return override, True
    if name == "float32":
        return float32_profile(), False
    if name == "bfloat16":
        return bfloat16_profile(), False
    if name == "float64":
        return float64_profile(), False
    if name in ("int8", "uint8", "bool"):
        return plan_pipeline("zlib_backend"), False
    return numeric_profile(), False


def _to_numeric_stream(arr: np.ndarray):
    flat = np.ascontiguousarray(arr).reshape(-1)
    if flat.dtype == np.bool_:
        flat = flat.view(np.uint8)
    if str(flat.dtype) == "bfloat16":
        flat = flat.view(np.uint16)
    if flat.dtype.kind == "f":
        flat = flat.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[flat.dtype.itemsize])
    if flat.dtype.kind in "iu":
        width = flat.dtype.itemsize
        return numeric(flat.view({1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[width]))
    raise TypeError(f"unsupported checkpoint dtype {arr.dtype}")


def compress_leaf(arr: np.ndarray) -> bytes:
    plan, trained = _plan_for_dtype(arr.dtype)
    stream = _to_numeric_stream(arr)
    if not trained:
        return _enc_session(plan).compress(stream)
    try:
        return _enc_session(plan).compress(stream)
    except Exception:
        # plans trained by `repro train` on raw sample files start from a
        # SERIAL input (their frontend re-types the bytes); numeric leaves
        # feed them as raw bytes instead — the frame stays self-describing
        # either way, so restore is unchanged
        return _enc_session(plan).compress(stream.as_serial())


def decompress_leaf(frame: bytes, shape, dtype) -> np.ndarray:
    (stream,) = _dec_session().decompress(frame)
    raw = stream.content_bytes()
    if str(dtype) == "bfloat16":
        import ml_dtypes

        return np.frombuffer(raw, dtype=ml_dtypes.bfloat16).reshape(shape).copy()
    out = np.frombuffer(raw, dtype=np.dtype(dtype) if str(dtype) != "bool" else np.uint8)
    if str(dtype) == "bool":
        out = out.astype(np.bool_)
    return out.reshape(shape).copy()


# ---------------------------------------------------------------- save/load
def save_checkpoint(
    directory: Path, step: int, tree: Any, metadata: Optional[dict] = None
) -> dict:
    directory = Path(directory)
    tmp = directory / f"step_{step:010d}.tmp"
    final = directory / f"step_{step:010d}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    leaves = []
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    t0 = time.time()
    raw_total = comp_total = 0
    for i, (path, leaf) in enumerate(flat):
        arr = np.asarray(jax.device_get(leaf))
        frame = compress_leaf(arr)
        fname = f"leaf_{i:05d}.ozl"
        (tmp / fname).write_bytes(frame)
        crash_point("ckpt.leaf")
        raw_total += arr.nbytes
        comp_total += len(frame)
        leaves.append(
            {
                "key": _leaf_key(path),
                "file": fname,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "raw_bytes": int(arr.nbytes),
                "compressed_bytes": len(frame),
                "crc32": zlib.crc32(frame) & 0xFFFFFFFF,
            }
        )
    manifest = {
        "step": step,
        "created": time.time(),
        "save_seconds": round(time.time() - t0, 3),
        "raw_bytes": raw_total,
        "compressed_bytes": comp_total,
        "ratio": round(raw_total / max(comp_total, 1), 4),
        "metadata": metadata or {},
        "leaves": leaves,
    }
    (tmp / MANIFEST).write_text(json.dumps(manifest, indent=1))
    crash_point("ckpt.manifest")
    os.replace(tmp, final)  # atomic publish
    crash_point("ckpt.publish.after")
    return manifest


def _valid_manifest(step_dir: Path) -> Optional[dict]:
    mpath = step_dir / MANIFEST
    if not mpath.exists():
        return None
    try:
        manifest = json.loads(mpath.read_text())
        for leaf in manifest["leaves"]:
            f = step_dir / leaf["file"]
            if not f.exists():
                return None
        return manifest
    except Exception:
        return None


def restore_checkpoint(
    directory: Path, step: Optional[int] = None, *, verify_crc: bool = True
) -> Tuple[Dict[str, np.ndarray], dict]:
    """Returns ({leaf_key: array}, manifest).  Use restore_tree to rebuild
    a concrete pytree structure."""
    directory = Path(directory)
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no valid checkpoint under {directory}")
    step_dir = directory / f"step_{step:010d}"
    manifest = _valid_manifest(step_dir)
    if manifest is None:
        raise FileNotFoundError(f"checkpoint step {step} invalid/missing")
    out: Dict[str, np.ndarray] = {}
    for leaf in manifest["leaves"]:
        frame = (step_dir / leaf["file"]).read_bytes()
        if verify_crc and (zlib.crc32(frame) & 0xFFFFFFFF) != leaf["crc32"]:
            raise IOError(f"checkpoint leaf {leaf['key']} corrupt (crc mismatch)")
        out[leaf["key"]] = decompress_leaf(frame, tuple(leaf["shape"]), leaf["dtype"])
    return out, manifest


def restore_tree(directory: Path, like: Any, step: Optional[int] = None, *, shardings=None):
    """Rebuild a pytree shaped `like` (tree of arrays or SDS), optionally
    device_put with per-leaf shardings (elastic restore onto any mesh)."""
    leaves_by_key, manifest = restore_checkpoint(directory, step)
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    shard_flat = None
    if shardings is not None:
        shard_flat = jax.tree_util.tree_flatten(shardings)[0]
    out = []
    for i, (path, leaf) in enumerate(flat):
        key = _leaf_key(path)
        if key not in leaves_by_key:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = leaves_by_key[key]
        want_dtype = getattr(leaf, "dtype", arr.dtype)
        if str(arr.dtype) != str(want_dtype):
            arr = arr.astype(want_dtype)
        if shard_flat is not None:
            out.append(jax.device_put(arr, shard_flat[i]))
        else:
            out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, [x for x in out]), manifest


def latest_step(directory: Path) -> Optional[int]:
    directory = Path(directory)
    if not directory.exists():
        return None
    steps = []
    for d in directory.iterdir():
        if d.name.startswith("step_") and not d.name.endswith(".tmp"):
            try:
                s = int(d.name[5:])
            except ValueError:
                continue
            if _valid_manifest(d):
                steps.append(s)
    return max(steps) if steps else None


class CheckpointManager:
    """keep-K, interval-based, optionally async checkpointing with resume."""

    def __init__(
        self,
        directory,
        *,
        save_interval: int = 100,
        keep: int = 3,
        async_save: bool = False,
    ):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.save_interval = save_interval
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        self.history: list = []

    def should_save(self, step: int) -> bool:
        return step > 0 and step % self.save_interval == 0

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def save(self, step: int, tree: Any, metadata: Optional[dict] = None) -> None:
        self.wait()
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)

        def work():
            m = save_checkpoint(self.directory, step, host_tree, metadata)
            self.history.append(m)
            self._gc()

        if self.async_save:
            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()
        else:
            work()

    def _gc(self) -> None:
        steps = sorted(
            int(d.name[5:])
            for d in self.directory.iterdir()
            if d.name.startswith("step_") and not d.name.endswith(".tmp")
        )
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(self.directory / f"step_{s:010d}", ignore_errors=True)

    def latest_step(self) -> Optional[int]:
        return latest_step(self.directory)

    def restore_or_none(self, like: Any, *, shardings=None):
        step = self.latest_step()
        if step is None:
            return None
        tree, manifest = restore_tree(self.directory, like, step, shardings=shardings)
        return step, tree, manifest
