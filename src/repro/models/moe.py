"""Mixture-of-Experts with expert parallelism (EP) over the ``model`` axis.

Routing follows the paper's sMVM philosophy: expert weights are static,
flash/"QLC"-resident tensors; the *router* is a controller op.  Token dispatch
uses sort + per-expert capacity gather (dropping MoE) so compiled FLOPs scale
with *active* experts — no dense all-expert compute.

Two sharding strategies, chosen per config:
  * ``ep``  — experts sharded over the axis (requires n_experts % axis == 0);
    each shard routes/computes only its local experts, partial outputs
    combine with one psum (the EP all-reduce).
  * ``etp`` — expert-tensor-parallel: all experts local, FFN dim sharded
    (for n_experts < axis, e.g. Grok's 8 experts on a 16-way axis); same
    single-psum combine.

Outside a mesh (CPU smoke tests) the same code runs with axis size 1.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = dict[str, Any]
CAPACITY_FACTOR = 2.0


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 6)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * scale,
        "w_up": jax.random.normal(ks[1], (E, d, ff), dtype) * scale,
        "w_down": jax.random.normal(ks[2], (E, ff, d), dtype) / math.sqrt(ff),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = jax.random.normal(ks[3], (E, d, ff), dtype) * scale
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts,
                                 cfg.mlp_type, dtype)
    return p


def _capacity(n_slots: int, n_experts: int) -> int:
    return max(1, math.ceil(n_slots / n_experts * CAPACITY_FACTOR))


def _q8_rows(x: jax.Array):
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, s


def _int8_expert_mm(x: jax.Array, w_q: jax.Array, w_s: jax.Array,
                    out_dtype) -> jax.Array:
    """[E,C,d] x int8 [E,d,f] -> [E,C,f]: W8A8, int32 accumulate (the PIM
    array's own arithmetic — expert weights are never dequantized to float).
    """
    x_q, x_s = _q8_rows(x)
    acc = jnp.einsum("ecd,edf->ecf", x_q.astype(jnp.int8), w_q,
                     preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_s * w_s[:, None, :]).astype(out_dtype)


def _expert_mm(x: jax.Array, p: Params, nm: str) -> jax.Array:
    if nm + "_q" in p:
        return _int8_expert_mm(x, p[nm + "_q"], p[nm + "_s"], x.dtype)
    return jnp.einsum("ecd,edf->ecf", x, p[nm].astype(x.dtype))


def _expert_ffn(xe: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """xe: [E_loc, C, d] -> [E_loc, C, d] through the local expert stack."""
    up = _expert_mm(xe, p, "w_up")
    if cfg.mlp_type == "swiglu":
        gate = _expert_mm(xe, p, "w_gate")
        h = jax.nn.silu(gate) * up
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.gelu(up)
    return _expert_mm(h, p, "w_down")


def moe_local(p: Params, x: jax.Array, cfg: ModelConfig, *,
              e_first: int | jax.Array = 0,
              n_local: int | None = None,
              shared_scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """Per-shard MoE.  x: [N, d] local tokens (replicated over the model axis).

    ``p`` holds the *already-local* expert weights (shard_map slices them per
    its in_specs): EP -> [E_loc, d, ff]; etp -> [E, d, ff_loc].  Routing is
    global; ``(e_first, n_local)`` select which expert ids are local.
    Returns (partial_out [N, d], aux_loss); the caller psums partial_out.
    """
    N, d = x.shape
    E, k = cfg.n_experts, cfg.n_experts_active
    n_local = n_local if n_local is not None else E

    logits = (x.astype(jnp.float32) @ p["router"])              # controller op
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (N * k)
    aux = E * jnp.sum(me * ce)

    slots = N * k
    slot_e = topi.reshape(-1)
    slot_w = topw.reshape(-1)
    slot_tok = jnp.arange(slots) // k
    local = (slot_e >= e_first) & (slot_e < e_first + n_local)
    lid = jnp.where(local, slot_e - e_first, n_local)           # n_local = drop bin
    order = jnp.argsort(lid)
    s_lid, s_tok, s_w = lid[order], slot_tok[order], slot_w[order]
    counts = jnp.zeros((n_local + 1,), jnp.int32).at[s_lid].add(1)[:n_local]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)])[:n_local]
    cap = _capacity(slots, E)

    def take(start, count):
        idx = start + jnp.arange(cap)
        valid = jnp.arange(cap) < count
        idx = jnp.where(valid, idx, 0)
        return s_tok[idx], s_w[idx] * valid, valid

    toks, ws, valid = jax.vmap(take)(starts, counts)            # [E_loc, cap]
    xe = x[toks] * valid[..., None].astype(x.dtype)             # gather

    ye = _expert_ffn(xe, p, cfg)
    ye = ye * ws[..., None].astype(ye.dtype)

    out = jnp.zeros((N, d), ye.dtype).at[toks.reshape(-1)].add(ye.reshape(-1, d))
    if cfg.n_shared_experts and "shared" in p:
        # shared_scale compensates for replication across axes the caller
        # will psum over (resident-EP mode)
        out = out + shared_scale * L.apply_mlp(p["shared"], x, cfg.mlp_type)
    return out, aux


def ep_capable(cfg: ModelConfig, axis_size: int) -> bool:
    return cfg.n_experts % axis_size == 0 and cfg.n_experts >= axis_size


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig,
              axis_name: str | None = None,
              reduce_fn=None) -> tuple[jax.Array, jax.Array]:
    """MoE over [B, T, d].  Inside shard_map pass ``axis_name='model'``;
    expert weights must already be the local shard (see moe_local).
    ``reduce_fn`` selects the combine collective (ring psum vs H-tree)."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    if axis_name is None:
        out, aux = moe_local(p, xf, cfg)
    else:
        ax = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        if ep_capable(cfg, ax):
            n_local = cfg.n_experts // ax
            out, aux = moe_local(p, xf, cfg, e_first=idx * n_local,
                                 n_local=n_local)
        else:   # etp: all experts local, FFN dim pre-sliced by shard_map
            out, aux = moe_local(p, xf, cfg)
        out = reduce_fn(out) if reduce_fn is not None else jax.lax.psum(out, axis_name)
        aux = jax.lax.pmean(aux, axis_name)
    return out.reshape(B, T, d).astype(x.dtype), aux
