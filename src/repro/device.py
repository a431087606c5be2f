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
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "on_tpu", "use_compile_cache"]

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
