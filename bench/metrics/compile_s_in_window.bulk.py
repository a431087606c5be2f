"""Seconds of backend compilation that finished inside the window
(jax.monitoring /jax/core/compile/backend_compile_duration)."""
from bench.measure import compile_s as read  # noqa: F401
