"""The checkpoint cell ``olmoe-ckpt.save`` on the CPU at a small size: its
configuration keeps the registered OLMoE-1B-7B widths; every item saved
through the device backend gives the host backend's frame, which the plain
reference decodes exactly, with no float_split node left on the host; and
the kernel readers read the float_split and entropy work of such a run."""
import dataclasses
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, reference, work
from bench.common import find, load_json, load_module, manifest
from bench.tests.test_bench_faults import small_olmoe

SEED = 2**31 + 1515
HBM = 819e9


@pytest.fixture(scope="module")
def olmoe():
    return load_module("configs", "olmoe-1b-7b-ckpt")


def test_configuration_keeps_the_registered_widths(olmoe):
    from repro.configs.olmoe_1b_7b import CFG

    cfg = load_json("configs", "olmoe-1b-7b-ckpt")
    assert (cfg["hidden_size"], cfg["intermediate_size"]) == (CFG.d_model, CFG.d_ff)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"]) == (CFG.n_experts, CFG.top_k)
    assert cfg["vocab_size"] == CFG.vocab
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (CFG.n_heads, CFG.n_kv_heads)
    assert cfg["published"]["num_hidden_layers"] == CFG.n_layers
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 1
    assert sum(int(np.prod(s)) for _, s in olmoe.leaves(cfg)) == 52_446_208


def _sessions(items, backend):
    from repro.codecs.profiles import resolve_profile_spec
    from repro.core import CompressorSession

    return {it.plan: CompressorSession(
        dataclasses.replace(resolve_profile_spec(it.profile), name=it.plan), backend=backend)
        for it in items}


def _save(items, sessions):
    from repro.core import numeric

    return [sessions[it.plan].compress(numeric(it.data), chunk_bytes=it.chunk_bytes)
            for it in items]


@pytest.fixture(scope="module")
def saved(olmoe):
    """One save of the small state through each backend, with the device
    sessions' counters and the float_split elements each backend split."""
    from repro.codecs.floats import float_split_info

    items = olmoe.items(small_olmoe(), SEED)
    host, device = _sessions(items, "host"), _sessions(items, "device")
    try:
        want = _save(items, host)
        before = float_split_info()
        got = _save(items, device)
        split = float_split_info()
        stats = harness.merged_stats(device.values())
    finally:
        for s in list(host.values()) + list(device.values()):
            s.close()
    grew = {b: {f: n - before[b].get(f, 0) for f, n in per.items()} for b, per in split.items()}
    checked = [reference.mismatched_bytes(f, it.data) for it, f in zip(items, got)]
    return SimpleNamespace(items=items, host=want, device=got, stats=stats, split=grew,
                           diffs=[d for d, _ in checked], records=[r for _, r in checked])


def test_device_save_gives_the_host_frames_and_decodes_exactly(saved):
    assert len(saved.items) == 48
    for it, fd, fh, diff in zip(saved.items, saved.device, saved.host, saved.diffs):
        assert fd == fh, it.name
        assert diff == 0, it.name


def test_no_float_split_node_runs_on_the_host(saved):
    nodes = saved.stats["nodes"]
    assert "float_split" not in nodes.get("host", {})
    assert nodes["device"]["float_split"] == saved.stats["chunks"] + sum(
        1 for it in saved.items if it.nbytes <= it.chunk_bytes)
    bf16 = sum(it.data.size for it in saved.items if it.data.dtype == np.uint16)
    fp32 = sum(it.data.size for it in saved.items if it.data.dtype == np.uint32)
    assert saved.split["device"] == {0: bf16, 2: fp32}


def _run(saved, device_nodes=None, module_s=None):
    """A traced run of one pass over the saved frames, as the harness hands
    it to the readers: node records per distinct frame, the sessions'
    counters and the trace's device time per compiled program."""
    records = [(recs, 1) for recs in saved.records]
    nodes = {"device": dict(saved.stats["nodes"]["device"], **(device_nodes or {})),
             "host": saved.stats["nodes"].get("host", {})}
    return SimpleNamespace(
        records=records, in_window={"nodes": nodes}, peaks={"hbm_bytes_per_s": HBM},
        trace=SimpleNamespace(module_s=module_s or {"jit_float_split": 0.01,
                                                    "jit_histogram_exact": 0.01,
                                                    "jit_pack_bits": 0.01}))


def test_float_split_roofline_reads_the_device_nodes_work(saved):
    reader = load_module("metrics", "float_split_roofline")
    run = _run(saved)
    want = sum(work.node_bytes(r) for recs, _ in run.records for r in recs
               if r.codec == "float_split")
    assert want > sum(it.nbytes for it in saved.items)  # every input byte is split
    assert reader.read(run) == pytest.approx(100.0 * want / HBM / 0.01)
    assert load_module("metrics", "entropy_roofline").read(run) > 0


def test_float_split_roofline_reads_none_on_a_count_mismatch(saved):
    reader = load_module("metrics", "float_split_roofline")
    n = saved.stats["nodes"]["device"]["float_split"]
    assert reader.read(_run(saved, {"float_split": n + 1})) is None
    # a program that splits only float32 on the device (bf16 on the host)
    fp32 = sum(-(-it.nbytes // it.chunk_bytes) for it in saved.items if it.data.dtype == np.uint32)
    assert reader.read(_run(saved, {"float_split": fp32})) is None
    assert reader.read(_run(saved, module_s={"jit_pack_bits": 0.01})) is None


def test_sound_save_run_is_correct_and_reports_its_metrics():
    cell = find(manifest()["workloads"], "olmoe-ckpt.save", "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe-1b-7b-ckpt", "bulk", 1)
    res = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(), cfg=small_olmoe())
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"compress_mibps", "ratio", "setup_s"}
    traced = {m["name"] for m in harness.metric_names(manifest(), cell["name"], True)}
    assert {"float_split_roofline", "entropy_roofline", "prefetch_hit_share.bulk"} <= traced
    assert "numeric_roofline" not in traced
