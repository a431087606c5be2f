"""Pod-level data parallelism with compressed gradient collectives (§Perf/H3).

Wraps an LM loss in a PARTIAL-MANUAL shard_map: the 'pod' axis is manual
(so we control the cross-pod gradient reduction and can compress its
payload), while 'data'/'model' stay automatic (XLA SPMD shards the per-pod
computation exactly as in the baseline step).

Cross-pod wire bytes per step:
    none    : fp32 psum            -> 4 B/param   (baseline)
    bf16    : bf16 psum            -> 2 B/param
    int8_ef : int8 all-gather + f32/256 scales -> ~1.016 B/param,
              error feedback keeps the accumulated update unbiased.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import transformer

from . import grad_compress as gc
from .optimizer import Optimizer


def make_pod_dp_train_step(cfg, optimizer: Optimizer, mesh: Mesh, method: str):
    """Returns (step_fn, in_specs, out_specs) for jit under `mesh`.

    step_fn(params, opt_state, ef_state, batch) -> (params, opt_state,
    ef_state, loss).  params/opt replicated over 'pod' (their intra-pod
    data/model sharding is untouched: those axes are auto).  batch sharded
    over 'pod' on dim 0; ef_state sharded over 'pod' (per-pod residual).
    """

    def body(params, opt_state, ef_state, batch):
        # per-pod loss on this pod's batch shard (data/model axes stay auto)
        loss, grads = jax.value_and_grad(transformer.loss_fn)(params, batch, cfg)
        ef_local = jax.tree.map(lambda e: e[0], ef_state)  # drop pod axis
        grads, ef_local = gc.compressed_psum(grads, "pod", method, ef_local)
        npods = jax.lax.psum(1, "pod")
        grads = jax.tree.map(lambda g: g / npods, grads)
        params, opt_state = optimizer.update(grads, opt_state, params)
        loss = jax.lax.pmean(loss, "pod")
        ef_state = jax.tree.map(lambda e: e[None], ef_local)
        return params, opt_state, ef_state, loss

    rep = P()  # replicated over pod; data/model placement handled by auto
    batch_spec = {"tokens": P("pod"), "labels": P("pod")}
    in_specs = (rep, rep, P("pod"), batch_spec)
    out_specs = (rep, rep, P("pod"), rep)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names={"pod"},
        check_vma=False,
    )


def make_ef_state_specs(params_sds, n_pods: int):
    """EF residual mirrors params with a leading (n_pods,) axis; shard_map's
    P('pod') in_spec gives each pod its own residual slice."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods,) + tuple(s.shape), jnp.float32),
        params_sds,
    )
