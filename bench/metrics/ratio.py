"""Input bytes over stored bytes.  Closed loops: over the configuration's
whole data, each item counted once with the frame the timed path last made of
it.  Open loops: over every request answered in the window."""


def read(run):
    if run.mix["loop"] == "closed":
        raw = sum(it.nbytes for it in run.inputs)
        stored = sum(run.frames.stored_bytes(k) for k in range(len(run.inputs)))
    else:
        raw = sum(c.nbytes for c in run.calls if c.error is None)
        stored = sum(c.out_bytes for c in run.calls if c.error is None)
    return raw / stored if stored else None
