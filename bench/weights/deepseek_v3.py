"""Seeded random weights of a DeepSeek-V3 stack (MLA attention in every
layer, dense SwiGLU layers first, then MoE layers with a sigmoid router, a
shared expert and this chip's share of the routed experts): the weight
builder that a configuration's ``family_module`` names.

As in :mod:`bench.weights.dense_decoder`, each tensor comes from its own
folded key and is built from random bits by exact arithmetic, so the
program's stacked tree (:func:`program_params`) and the reference's
one-layer draws (:func:`layer_weights`, :func:`expert_weights`) hold the
same numbers.  A routed expert's weights are drawn from its global id, so
expert 43 is the same whichever shard holds it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dense_decoder as D

ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
MLP = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
# fold_in tags: tensors outside the layer stack, then inside a layer
_EMBED, _HEAD, _LN_F, _LAYERS = 0, 1, 2, 3
_ROUTER, _BIAS, _EXPERTS = 20, 21, 200


def attn_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """[in, out] of each MLA linear of one layer."""
    d, h = m["d_model"], m["n_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    r = m["kv_lora_rank"]
    return {"wq_a": (d, m["q_lora_rank"]),
            "wq_b": (m["q_lora_rank"], h * (dn + dr)),
            "wkv_a": (d, r + dr), "wkv_b": (r, h * (dn + dv)),
            "wo": (h * dv, d)}


def ffn_shapes(d: int, ff: int, names=MLP) -> dict[str, tuple[int, int]]:
    gate, up, down = names
    return {gate: (d, ff), up: (d, ff), down: (ff, d)}


def is_moe(m: dict, i: int) -> bool:
    return m["n_experts"] > 0 and i >= m["first_dense_layers"]


def held_ids(m: dict) -> np.ndarray:
    """Global ids of the routed experts this chip holds."""
    n = m["n_experts_held"] or m["n_experts"]
    return m["expert_shard"] * n + np.arange(n)


def _linears(k, shapes: dict, first: int = 0) -> dict:
    return {name: D._normal(jax.random.fold_in(k, first + j), shape,
                            1.0 / math.sqrt(shape[0]))
            for j, (name, shape) in enumerate(shapes.items())}


def _bias(key, n):
    """Router correction bias uniform in [-1/16, 1/16), exact in float32."""
    bits = jax.random.bits(key, (n,), jnp.uint32) >> 23
    return (bits.astype(jnp.float32) - 256.0) * np.float32(2.0**-12)


def layer_weights(key, m: dict, i, moe: bool) -> dict:
    """Layer ``i`` (may be traced; ``moe`` says which kind it is), without
    its routed experts: bf16 linears scaled 1/sqrt(fan_in), float32 norm
    gains, and for a MoE layer the float32 router and bias."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LAYERS), i)
    d = m["d_model"]
    out = _linears(k, attn_shapes(m))
    if moe:
        ff = m["moe_d_ff"] * m["n_shared_experts"]
        out.update(_linears(k, ffn_shapes(d, ff, SHARED), first=10))
        out["router"] = D._normal(jax.random.fold_in(k, _ROUTER),
                                  (d, m["n_experts"]),
                                  1.0 / math.sqrt(d)).astype(jnp.float32)
        out["router_bias"] = _bias(jax.random.fold_in(k, _BIAS),
                                   m["n_experts"])
    else:
        out.update(_linears(k, ffn_shapes(d, m["d_ff"]), first=10))
    out["ln1"] = D._norm_scale(jax.random.fold_in(k, 100), d)
    out["ln2"] = D._norm_scale(jax.random.fold_in(k, 101), d)
    out["q_norm"] = D._norm_scale(jax.random.fold_in(k, 102),
                                  m["q_lora_rank"])
    out["kv_norm"] = D._norm_scale(jax.random.fold_in(k, 103),
                                   m["kv_lora_rank"])
    return out


def expert_weights(key, m: dict, i, e) -> dict:
    """Routed expert ``e`` (global id, may be traced) of layer ``i``."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LAYERS), i)
    k = jax.random.fold_in(jax.random.fold_in(k, _EXPERTS), e)
    return _linears(k, ffn_shapes(m["d_model"], m["moe_d_ff"]))


def embed(key, m: dict):
    return D._normal(jax.random.fold_in(key, _EMBED),
                     (m["vocab_size"], m["d_model"]), 0.02)


def head(key, m: dict):
    """[d, V] untied output head over this chip's slice of the vocabulary."""
    return D._normal(jax.random.fold_in(key, _HEAD),
                     (m["d_model"], m["vocab_size"]),
                     1.0 / math.sqrt(m["d_model"]))


def final_norm(key, m: dict):
    return D._norm_scale(jax.random.fold_in(key, _LN_F), m["d_model"])


def _slot(w: dict, moe: bool) -> dict:
    """One layer's flat draw in the program's nesting."""
    slot = {"ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {**{n: w[n] for n in ATTN},
                     "q_norm": {"scale": w["q_norm"]},
                     "kv_norm": {"scale": w["kv_norm"]}}}
    if moe:
        slot["moe"] = {"router": w["router"], "router_bias": w["router_bias"],
                       "shared": {n: w[s] for n, s in zip(MLP, SHARED)},
                       **{n: w[n] for n in MLP}}
    else:
        slot["mlp"] = {n: w[n] for n in MLP}
    return slot


def program_params(key, m: dict) -> dict:
    """The whole parameter tree in the program's layout (a group of dense
    layers, then a group of MoE layers holding this chip's experts).  Call
    under ``jax.jit``."""
    f, n = m["first_dense_layers"], m["n_layers"]
    held = jnp.asarray(held_ids(m))

    def moe_layer(i):
        w = layer_weights(key, m, i, True)
        w.update(jax.vmap(lambda e: expert_weights(key, m, i, e))(held))
        return w

    groups = ()
    if f:
        dense = jax.vmap(lambda i: layer_weights(key, m, i, False))(
            jnp.arange(f))
        groups += (_slot(dense, False),),
    if n > f:
        moe = jax.vmap(moe_layer)(jnp.arange(f, n))
        groups += (_slot(moe, True),),
    return {"embed": {"w": embed(key, m)},
            "ln_f": {"scale": final_norm(key, m)},
            "lm_head": {"w": head(key, m)},
            "groups": groups}
