"""float_split kernel: the codec work of its device nodes over peak HBM
bandwidth, over the device time of its compiled program ``jit_float_split``
(profiler trace).

The device twin splits NUMERIC width 4 at fmt 2 (float32) and NUMERIC width 2
at fmt 0 or 1 (bfloat16, float16); fmt 3 (float64) stays on the host.  A
node's work is ``work.node_bytes``: its input, and its sign, exponent and
mantissa planes as the codec defines them.  Nothing is read when no such node
ran, or when their count disagrees with the device float_split nodes the
sessions counted in the window (the work would be wrong)."""
from bench import work
from bench.reference import NUMERIC

DEVICE_FMTS = {4: (2,), 2: (0, 1)}  # input width -> fmt tags split on the device
MODULE = "jit_float_split"


def on_device(rec) -> bool:
    stype, width, _ = rec.ins[0]
    return (rec.codec == "float_split" and stype == NUMERIC
            and rec.header.get("fmt") in DEVICE_FMTS.get(width, ()))


def read(run):
    if run.trace is None or run.records is None:
        return None
    total = nodes = 0
    for recs, n_calls in run.records:
        for rec in recs:
            if on_device(rec):
                total += work.node_bytes(rec) * n_calls
                nodes += n_calls
    if run.in_window is not None:
        if run.in_window["nodes"].get("device", {}).get("float_split", 0) != nodes:
            return None
    device_s = run.trace.module_s.get(MODULE, 0.0)
    return work.roofline_share(total, device_s, run.peaks["hbm_bytes_per_s"])
