"""Input MiB of every compress call completed in the window, over the
window's whole time (host clock)."""
from bench.measure import compress_mibps as read  # noqa: F401
