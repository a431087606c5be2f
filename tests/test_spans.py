"""The program's profiler spans and counters: one device-backend compress
under the profiler records a span in each layer on the host plane, and the
copy and resolve counters move by what that call did."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro.core import compress, decompress, numeric, pipeline, resolve_cache_clear
from repro.core.engine import resolve_cache_info
from repro.device import transfer_info
from repro.service.metrics import render_prometheus

REPO = Path(__file__).resolve().parents[1]


def _host_span_names(trace_dir: Path):
    from jax.profiler import ProfileData

    (path,) = trace_dir.rglob("*.xplane.pb")
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events if e.name.startswith("ozl."))
    return names


def test_one_device_compress_records_a_span_in_each_layer(tmp_path):
    import jax

    n = 4096
    col = numeric(np.random.default_rng(3).integers(0, 1 << 30, n).astype(np.uint32))
    resolve_cache_clear()
    before_t, before_r = transfer_info(), resolve_cache_info()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        frame = compress(pipeline("transpose"), col, backend="device")
    finally:
        jax.profiler.stop_trace()
    after_t, after_r = transfer_info(), resolve_cache_info()
    assert decompress(frame)[0].content_bytes() == col.content_bytes()

    names = _host_span_names(tmp_path)
    assert {"ozl.resolve", "ozl.encode.device.transpose", "ozl.h2d", "ozl.d2h",
            "ozl.wire.write_frame"} <= names, names
    assert not any(s.startswith("ozl.encode.host.") for s in names), names
    # the transpose twin copies the (n, 4) bytes in and the (4, n) planes out
    assert after_t["h2d"] - before_t["h2d"] == 1
    assert after_t["d2h"] - before_t["d2h"] == 1
    assert after_t["h2d_bytes"] - before_t["h2d_bytes"] == 4 * n
    assert after_t["d2h_bytes"] - before_t["d2h_bytes"] == 4 * n
    assert after_r["misses"] - before_r["misses"] == 1
    assert after_r["miss_s"] > before_r["miss_s"]


def test_a_cache_hit_adds_no_resolve_time():
    col = numeric(np.arange(3000, dtype=np.uint16))
    compress(pipeline("delta"), col)
    before = resolve_cache_info()
    compress(pipeline("delta"), col)
    after = resolve_cache_info()
    assert after["hits"] == before["hits"] + 1
    assert after["miss_s"] == before["miss_s"]


def test_counters_reach_the_prometheus_rendering():
    text = render_prometheus({
        "resolve_cache": {"hits": 3, "misses": 2, "miss_s": 1.5},
        "transfers": {"h2d": 4, "d2h": 5, "h2d_bytes": 4096, "d2h_bytes": 8192},
    }).decode()
    assert "ozl_resolve_seconds_total 1.5" in text
    assert 'ozl_device_transfers_total{direction="h2d"} 4' in text
    assert 'ozl_device_transfer_bytes_total{direction="d2h"} 8192' in text


def test_host_only_compress_does_not_import_jax():
    """Spans cost a host-only caller nothing: without JAX loaded no trace can
    run, so the program's spans import nothing."""
    probe = (
        "import sys, numpy as np\n"
        "from repro.core import compress, numeric, pipeline\n"
        "compress(pipeline('delta', 'zigzag'), numeric(np.arange(999, dtype=np.uint32)))\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True,
                         timeout=120, env=dict(os.environ, PYTHONPATH=str(REPO / "src"),
                                               JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False"]
