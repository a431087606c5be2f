"""The one device decision and the placement of JAX's compile cache.

The cache cases run in a child interpreter: turning the persistent cache on
is process-wide, and this test process must keep its own JAX configuration.
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.device import CACHE_DIR, on_tpu

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.device import use_compile_cache
path = use_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir=None):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", PROBE], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_lands_in_the_environment_dir(tmp_path):
    cache = tmp_path / "jaxcache"
    path, configured = _probe(cache)
    assert path == configured == str(cache)
    assert any(cache.iterdir()), "nothing was cached where the variable says"


def test_compile_cache_defaults_to_one_fixed_ignored_path():
    assert CACHE_DIR == REPO / ".jax_cache"
    path, configured = _probe()
    assert path == configured == str(CACHE_DIR)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_kernel_selection_follows_the_one_decision():
    """Off the TPU the data path runs the jnp oracles, and a kernel asked for
    by name runs in interpret mode; the ops wrappers ask nothing else."""
    from repro.kernels import ops

    assert on_tpu() is False
    assert ops._pallas(None) is False and ops._pallas(True) is True
    assert ops._interpret() is True
