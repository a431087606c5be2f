"""Calibration tests documenting the dry-run measurement semantics that the
roofline analysis relies on (EXPERIMENTS.md §2):

  1. cost_analysis()['flops'] of an SPMD executable is PER-DEVICE,
  2. memory_analysis() argument sizes are PER-DEVICE (shards + replicas),
  3. post-SPMD HLO collectives carry per-device transfer shapes,
  4. while-loop (scan) bodies are counted ONCE by cost analysis — the
     documented undercount the roofline corrects by xN_layers.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def run4(body: str) -> str:
    script = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"\n'
        # newer jax returns cost_analysis() as a dict, older as a 1-list of dicts
        "def _cost(compiled):\n"
        "    ca = compiled.cost_analysis()\n"
        "    return ca[0] if isinstance(ca, (list, tuple)) else ca\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_cost_and_memory_are_per_device():
    out = run4(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((4,), ("data",))
        M = N = K = 1024
        sh_a = NamedSharding(mesh, P("data", None))
        sh_b = NamedSharding(mesh, P(None, None))
        with mesh:
            compiled = jax.jit(lambda a, b: a @ b, in_shardings=(sh_a, sh_b)).lower(
                jax.ShapeDtypeStruct((M, K), jnp.float32),
                jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
        flops = _cost(compiled)["flops"]
        # global 2*M*N*K = 2.147e9; per-device = /4
        assert abs(flops - 2 * M * N * K / 4) < 1e6, flops
        m = compiled.memory_analysis()
        # per-device args: a shard (1MB) + b replicated (4MB)
        assert abs(m.argument_size_in_bytes - (M * K + K * N + 0) * 4 // 4 - 3 * K * N) < (1 << 20)
        print("PER_DEVICE_OK", flops, m.argument_size_in_bytes)
        """
    )
    assert "PER_DEVICE_OK" in out


def test_scan_bodies_counted_once():
    """The undercount the roofline's xn_layers correction exists for."""
    out = run4(
        """
        import jax, jax.numpy as jnp
        N_STEPS = 8
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, x, None, length=N_STEPS)
            return out
        def f_unrolled(x, w):
            for _ in range(N_STEPS):
                x = jnp.tanh(x @ w)
            return x
        sds = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        fl_loop = _cost(jax.jit(f).lower(sds, sds).compile())["flops"]
        fl_unrl = _cost(jax.jit(f_unrolled).lower(sds, sds).compile())["flops"]
        ratio = fl_unrl / fl_loop
        assert 4 <= ratio <= N_STEPS * 1.5, (fl_loop, fl_unrl)
        print("SCAN_UNDERCOUNT_OK", ratio)
        """
    )
    assert "SCAN_UNDERCOUNT_OK" in out


def test_collective_parse_and_cross_pod_split():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ag = f32[4,128]{1,0} all-gather(%x), replica_groups=[1,4]<=[4], dimensions={0}
  %ar-start = bf16[256]{0} all-reduce-start(%y), replica_groups={{0,2},{1,3}}
  %ar-done = bf16[256]{0} all-reduce-done(%ar-start)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 4 * 128 * 4
    assert out["all-reduce"] == 256 * 2  # -start counted, -done skipped
    assert out["total"] == out["all-gather"] + out["all-reduce"]

    # cross-pod split: explicit groups {0,2},{1,3} cross the half=2 boundary
    out2 = collective_bytes(hlo.replace("[1,4]<=[4]", "[2,2]<=[4]"), n_devices=512)
    assert "cross_pod" in out2

