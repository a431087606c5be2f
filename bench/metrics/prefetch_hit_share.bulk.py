"""Share of chunk draws that the session's prefetch hid behind in-flight
encodes, over the window (CompressorSession.stats prefetch_hits / (hits + misses))."""
from bench.measure import session_share


def read(run):
    return session_share(run, "prefetch_hits", "prefetch_misses")
