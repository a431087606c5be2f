"""Share of the traced window in which no operation ran on the device:
1 - busy / window (profiler trace)."""
from bench.measure import idle_share as read  # noqa: F401
