"""Share of executed nodes that ran on the host, over the window
(CompressorSession.stats["nodes"]: host / all)."""
from bench.measure import host_node_share as read  # noqa: F401
