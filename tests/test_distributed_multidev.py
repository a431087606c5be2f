"""Multi-(virtual-)device tests: sharded train step, compressed gradient
collectives, elastic mesh restore.  Each test runs in a subprocess because
XLA_FLAGS device-count must be set before jax initializes (the main test
process keeps 1 device, per the assignment's conftest rule)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(n_devices: int, body: str) -> str:
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=480,
        env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_lm_train_step_matches_single_device():
    out = run_with_devices(
        8,
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.cells import build_cell
        from repro.launch.mesh import make_host_mesh

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cell = build_cell("llama3.2-1b", "train_4k", mesh=mesh, reduced=True)
        # NOTE: reduced cell built against a mesh gets real shardings
        args = cell.make_real_args(jax.random.PRNGKey(0))
        with mesh:
            jitted = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
            )
            p1, o1, l1 = jitted(*args)
        # single-device reference
        cell1 = build_cell("llama3.2-1b", "train_4k", mesh=None, reduced=True)
        args1 = cell1.make_real_args(jax.random.PRNGKey(0))
        p1r, o1r, l1r = jax.jit(cell1.fn)(*args1)
        assert abs(float(l1) - float(l1r)) < 1e-4, (float(l1), float(l1r))
        print("LOSS_MATCH", float(l1))
        """,
    )
    assert "LOSS_MATCH" in out


def test_grad_compression_psum_accuracy_and_ef():
    out = run_with_devices(
        4,
        """
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed import grad_compress as gc

        mesh = jax.make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        g_local = jnp.asarray(rng.normal(size=(4, 1024)).astype(np.float32))
        exact = np.asarray(g_local).sum(0)

        @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))
        def red_bf16(g):
            out, _ = gc.compressed_psum({"g": g[0]}, "pod", "bf16")
            return out["g"][None]

        got = np.asarray(red_bf16(g_local))[0]
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        assert rel < 2e-2, rel
        print("BF16_REL", rel)

        # error-feedback residual is PER-DEVICE state: sharded on 'pod'
        ef0 = {"g": jnp.zeros((4, 1024), jnp.float32)}
        @partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")))
        def red_int8(g, ef):
            out, new_ef = gc.compressed_psum(
                {"g": g[0]}, "pod", "int8_ef", ef_state={"g": ef["g"][0]}
            )
            return out["g"][None], {"g": new_ef["g"][None]}

        got8, ef1 = red_int8(g_local, ef0)
        rel8 = np.abs(np.asarray(got8)[0] - exact).max() / np.abs(exact).max()
        assert rel8 < 5e-2, rel8
        # error feedback: residual captured, nonzero
        assert float(jnp.abs(ef1["g"]).max()) > 0
        print("INT8_REL", rel8)

        # EF unbiasedness over repeats: sum of (reduced_t) approaches sum of t*exact
        acc = np.zeros_like(exact); ef = ef0
        for t in range(20):
            r, ef = red_int8(g_local, ef)
            acc += np.asarray(r)[0]
        drift = np.abs(acc - 20 * exact).max() / np.abs(20 * exact).max()
        assert drift < 5e-3, drift
        print("EF_DRIFT", drift)
        """,
    )
    assert "EF_DRIFT" in out


def test_dryrun_entry_single_cell():
    """The dry-run module itself runs (512 virtual devices, one cheap cell)."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "repro.launch.dryrun",
            "--arch",
            "sasrec",
            "--shape",
            "serve_p99",
            "--force",
        ],
        capture_output=True,
        text=True,
        timeout=480,
        env=env,
        cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[ok]" in out.stdout


def test_elastic_checkpoint_across_meshes(tmp_path):
    out = run_with_devices(
        8,
        f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.checkpoint import save_checkpoint, restore_tree

        tree = {{"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)}}
        # save from a (4,2) mesh layout
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        sharded = jax.device_put(tree["w"], NamedSharding(mesh_a, P("data", "model")))
        save_checkpoint("{tmp_path}", 1, {{"w": sharded}})
        # restore onto a DIFFERENT mesh shape (8,1) — elastic rescale
        mesh_b = jax.make_mesh((8, 1), ("data", "model"))
        sh_b = {{"w": NamedSharding(mesh_b, P("data", None))}}
        restored, _ = restore_tree("{tmp_path}", tree, 1, shardings=sh_b)
        assert np.array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
        print("ELASTIC_OK", restored["w"].sharding)
        """,
    )
    assert "ELASTIC_OK" in out
