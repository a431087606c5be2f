"""One chip's share of an OLMoE-1B-7B layer's training state, made from the
seed on the device in one jitted call and saved leaf by leaf."""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.common import Item

ROLES = ("exp_avg", "exp_avg_sq")


def leaves(cfg: dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, this chip's shape) of every parameter of one decoder layer."""
    h, ff, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    k = cfg["deployment"]["chips_per_layer"]
    attn = [(f"self_attn.{p}_proj.weight", (h // k, h)) for p in "qkvo"]
    norms = [(f"{n}.weight", (h // k,)) for n in
             ("self_attn.q_norm", "self_attn.k_norm", "input_layernorm",
              "post_attention_layernorm")]
    moe = [("mlp.gate.weight", (e // k, h)),
           ("mlp.experts.gate_proj.weight", (e // k, ff, h)),
           ("mlp.experts.up_proj.weight", (e // k, ff, h)),
           ("mlp.experts.down_proj.weight", (e // k, h, ff))]
    return attn + norms + moe


def state(cfg: dict, seed: int):
    """Per leaf: (bf16 param, fp32 master, fp32 exp_avg, fp32 exp_avg_sq),
    as host arrays, generated by one jitted program on the default device."""
    import jax
    import jax.numpy as jnp

    shapes = [s for _, s in leaves(cfg)]
    std = float(cfg["initializer_range"])
    words = np.random.SeedSequence(seed).generate_state(2)

    @jax.jit
    def make(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        out = []
        for j, shape in enumerate(shapes):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, j), 3)
            master = std * jax.random.normal(k1, shape, jnp.float32)
            m = 1e-3 * jax.random.normal(k2, shape, jnp.float32)
            v = jnp.square(1e-3 * jax.random.normal(k3, shape, jnp.float32))
            out.append((master.astype(jnp.bfloat16), master, m, v))
        return out

    return jax.device_get(make(jnp.asarray(words, dtype=jnp.uint32)))


def items(cfg: dict, seed: int) -> List[Item]:
    """Model state first, then optimizer state, as the checkpoint saves them."""
    names = [n for n, _ in leaves(cfg)]
    arrays = state(cfg, seed)
    chunk = int(cfg["chunk_bytes"])
    bf16, fp32 = cfg["profiles"]["bfloat16"], cfg["profiles"]["float32"]
    out = [Item(f"{n}.param", bf16, bf16, np.asarray(a[0]).view(np.uint16).reshape(-1), chunk)
           for n, a in zip(names, arrays)]
    for n, a in zip(names, arrays):
        out.append(Item(f"{n}.master", fp32, fp32, np.asarray(a[1]).view(np.uint32).reshape(-1), chunk))
        for role, arr in zip(ROLES, a[2:]):
            out.append(Item(f"{n}.{role}", fp32, fp32, np.asarray(arr).view(np.uint32).reshape(-1), chunk))
    return out


def control(data: np.ndarray) -> np.ndarray:
    """The step below the stated precision: fp32 leaves (as uint32 bit
    patterns) rounded to bfloat16, to nearest even.  bf16 leaves stay."""
    if data.dtype != np.uint32:
        return data
    lsb = (data >> np.uint32(16)) & np.uint32(1)
    return (data + np.uint32(0x7FFF) + lsb) & np.uint32(0xFFFF0000)
