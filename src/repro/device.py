"""The one device decision, and where compiled device programs are kept.

``on_tpu()`` is the only place the code asks which platform JAX runs on.
On a TPU the device backend's Pallas kernels compile to Mosaic, never to
interpret mode.  Elsewhere the data path runs their jit'd jnp oracles
(``repro.kernels.ref``), and a Pallas kernel runs in interpret mode only
where a caller asks for it by name (``use_pallas=True``: the kernel tests).

``use_compile_cache()`` places JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing here overrides
it; otherwise the cache lives at one fixed path inside the checkout
(``.jax_cache/``, ignored by git), so a later process on the same checkout
finds the programs again.

``span(name)`` is a host span in the profiler's own trace (a TraceMe, what
``jax.profiler.TraceAnnotation`` records), on the clock of the device
planes.  The program's spans all start with ``ozl.``; with no trace running
a span records nothing.  ``to_device(x)`` and ``to_host(y)`` are the
device twins' only host<->device copies: each opens its span (``ozl.h2d``,
``ozl.d2h``) and counts its bytes in ``transfer_info()``.
"""
from __future__ import annotations

import contextlib
import os
import sys
import threading
from pathlib import Path

import numpy as np

__all__ = ["CACHE_DIR", "on_tpu", "use_compile_cache", "span", "to_device",
           "to_host", "transfer_info"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (Pallas compiles to Mosaic)."""
    import jax

    return jax.default_backend() == "tpu"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache -> its directory.

    Every compiled program is kept, however quick its compile: the device
    backend compiles one small program per kernel and shape, and together
    they are a large share of a cold start."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_NO_SPAN = contextlib.nullcontext()


def span(name: str):
    """A profiler span named ``name`` around a ``with`` block.

    Without JAX loaded no trace can be running, so nothing is imported and
    the span is a no-op: host-only callers never pay for importing JAX."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return _NO_SPAN
    return profiler.TraceAnnotation(name)


_transfer_lock = threading.Lock()
_transfers = {"h2d_bytes": 0, "d2h_bytes": 0, "h2d": 0, "d2h": 0}


def _count(way: str, nbytes: int) -> None:
    with _transfer_lock:
        _transfers[way] += 1
        _transfers[way + "_bytes"] += int(nbytes)


def transfer_info() -> dict:
    """Host<->device copies made by ``to_device``/``to_host`` in this process:
    counts and bytes each way."""
    with _transfer_lock:
        return dict(_transfers)


def to_device(x):
    """Copy the host array ``x`` to the default device -> a JAX array.

    JAX may return once the bytes are staged, before the transfer itself
    ends: the span ``ozl.h2d`` covers the call, and any remainder of the
    copy is waited for by the first program that reads the array."""
    import jax.numpy as jnp

    with span("ozl.h2d"):
        y = jnp.asarray(x)
    _count("h2d", y.nbytes)
    return y


def to_host(y) -> np.ndarray:
    """Copy the device array ``y`` to a host ``numpy`` array.

    While a trace runs, the program that computes ``y`` is waited for before
    the span ``ozl.d2h`` opens, so the span is the copy and not the kernel;
    without a trace the copy itself waits, as ``np.asarray`` always does."""
    import jax

    if jax.profiler.TraceAnnotation.is_enabled():
        jax.block_until_ready(y)
    with span("ozl.d2h"):
        out = np.asarray(y)
    _count("d2h", out.nbytes)
    return out


def _device_after_fork() -> None:
    """Re-arm the counters' lock in a forked child (a thread of the parent may
    have held it at the fork)."""
    global _transfer_lock
    _transfer_lock = threading.Lock()


if hasattr(os, "register_at_fork"):  # pragma: no branch - POSIX in CI
    os.register_at_fork(after_in_child=_device_after_fork)
