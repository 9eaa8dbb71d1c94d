"""Mixture-of-Experts with expert parallelism (EP) over the ``model`` axis.

Routing follows the paper's sMVM philosophy: expert weights are static,
flash/"QLC"-resident tensors; the *router* is a controller op.  Two routers,
chosen by ``cfg.moe_scoring``: softmax top-k, and DeepSeek-V3's sigmoid
router (a per-expert correction bias steers selection only, the top
``moe_topk_group`` of ``moe_n_group`` groups are searched, the chosen
experts' unbiased scores are normalised and scaled).

Dispatch is dropless: no routed (token, expert) pair is ever dropped.
Float expert stacks (prefill, training) sort the pairs by expert and run a
grouped product (``jax.lax.ragged_dot``), so compute follows the pairs
routed and not a worst-case capacity.  Int8 stacks (decode, verify) gather
a per-expert buffer as deep as the step's token count, which a token fills
at most once per expert, so it is exact.

Two sharding strategies, chosen per config:
  * ``ep``  — experts sharded over the axis (requires n_experts % axis == 0);
    each shard routes/computes only its local experts, partial outputs
    combine with one psum (the EP all-reduce).
  * ``etp`` — expert-tensor-parallel: all experts local, FFN dim sharded
    (for n_experts < axis, e.g. Grok's 8 experts on a 16-way axis); same
    single-psum combine.

Outside a mesh the layer is one chip's share of an expert-parallel group:
it routes over all ``n_experts`` and adds the part of the experts it holds
(``cfg.experts_held`` of them, shard ``cfg.expert_shard``); with every
expert held that is the whole layer.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = dict[str, Any]


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Router over all ``n_experts``; weights of the ``experts_held``."""
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    n = cfg.experts_held
    ks = jax.random.split(key, 6)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * scale,
        "w_up": jax.random.normal(ks[1], (n, d, ff), dtype) * scale,
        "w_down": jax.random.normal(ks[2], (n, ff, d), dtype) / math.sqrt(ff),
    }
    if cfg.moe_router_bias:
        p["router_bias"] = jax.random.normal(ks[5], (E,), jnp.float32) * 0.05
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = jax.random.normal(ks[3], (n, d, ff), dtype) * scale
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts,
                                 cfg.mlp_type, dtype)
    return p


def route(p: Params, x: jax.Array, cfg: ModelConfig):
    """Controller op: ``x`` [N, d] -> (weights [N, k], expert ids [N, k],
    Switch-style load-balance aux loss)."""
    N = x.shape[0]
    E, k = cfg.n_experts, cfg.n_experts_active
    logits = x.astype(jnp.float32) @ p["router"]
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"] if "router_bias" in p else scores
        G = cfg.moe_n_group
        if G > 1:
            per = choice.reshape(N, G, E // G)
            group = jax.lax.top_k(per, min(2, E // G))[0].sum(-1)   # [N, G]
            _, kept = jax.lax.top_k(group, cfg.moe_topk_group)
            keep = jnp.zeros((N, G), bool).at[
                jnp.arange(N)[:, None], kept].set(True)
            choice = jnp.where(jnp.repeat(keep, E // G, axis=1), choice,
                               -jnp.inf)
        _, topi = jax.lax.top_k(choice, k)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
    elif cfg.moe_scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
    else:
        raise ValueError(f"unknown router scoring {cfg.moe_scoring!r}")
    if cfg.moe_norm_topk:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_routed_scaling != 1.0:
        topw = topw * cfg.moe_routed_scaling
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (N * k)
    return topw, topi, E * jnp.sum(me * ce)


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


def _grouped_mm(x: jax.Array, p: Params, nm: str, sizes: jax.Array):
    """Rows of ``x`` sorted by expert, ``sizes[e]`` of them for expert e,
    through each one's float ``nm`` weights (rows past the groups are the
    caller's to mask)."""
    return jax.lax.ragged_dot(x, p[nm].astype(x.dtype), sizes)


def _grouped_ffn(xs: jax.Array, p: Params, cfg: ModelConfig,
                 sizes: jax.Array) -> jax.Array:
    up = _grouped_mm(xs, p, "w_up", sizes)
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(_grouped_mm(xs, p, "w_gate", sizes)) * up
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.gelu(up)
    return _grouped_mm(h, p, "w_down", sizes)


def moe_local_counted(p: Params, x: jax.Array, cfg: ModelConfig, *,
                      e_first: int | jax.Array = 0,
                      n_local: int | None = None,
                      shared_scale: float = 1.0,
                      valid: jax.Array | None = None):
    """Per-shard MoE.  x: [N, d] local tokens (replicated over the model axis).

    ``p`` holds the *already-local* expert weights (shard_map slices them per
    its in_specs, or the config holds a share): EP -> [E_loc, d, ff];
    etp -> [E, d, ff_loc].  Routing is global; ``(e_first, n_local)``
    select which expert ids are local.  Returns (partial_out [N, d],
    aux_loss, counts); the caller psums partial_out.  ``counts`` are int32
    scalars: ``local_pairs``, the routed pairs that landed on local
    experts, and ``experts_touched``, the local experts that received at
    least one; ``valid`` ([N] bool, optional) leaves rows out of the counts
    (padding), not out of the output.
    """
    N, d = x.shape
    k = cfg.n_experts_active
    n_local = n_local if n_local is not None else cfg.experts_held

    with jax.named_scope("moe_router"):
        topw, topi, aux = route(p, x, cfg)
        slots = N * k
        slot_e = topi.reshape(-1)
        slot_w = topw.reshape(-1)
        slot_tok = jnp.arange(slots) // k
        local = (slot_e >= e_first) & (slot_e < e_first + n_local)
        lid = jnp.where(local, slot_e - e_first, n_local)       # n_local = not here
        order = jnp.argsort(lid, stable=True)
        s_lid, s_tok, s_w = lid[order], slot_tok[order], slot_w[order]
        sizes = jnp.zeros((n_local + 1,), jnp.int32).at[s_lid].add(1)[:n_local]
        real = sizes if valid is None else jnp.zeros(
            (n_local + 1,), jnp.int32).at[lid].add(
                valid[slot_tok].astype(jnp.int32))[:n_local]
        counts = {"local_pairs": real.sum(),
                  "experts_touched": (real > 0).sum().astype(jnp.int32)}

    with jax.named_scope("moe_experts"):
        if "w_up_q" in p:
            # a token picks an expert at most once: N rows per expert hold
            # every pair, so nothing is dropped
            starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                      jnp.cumsum(sizes)])[:n_local]
            idx = starts[:, None] + jnp.arange(N)[None, :]
            filled = jnp.arange(N)[None, :] < sizes[:, None]    # [E_loc, N]
            idx = jnp.where(filled, idx, 0)
            toks, ws = s_tok[idx], s_w[idx] * filled
            xe = x[toks] * filled[..., None].astype(x.dtype)    # gather
            ye = _expert_ffn(xe, p, cfg) * ws[..., None].astype(x.dtype)
            toks, ye = toks.reshape(-1), ye.reshape(-1, d)
        else:
            ye = _grouped_ffn(x[s_tok], p, cfg, sizes)
            here = (s_lid < n_local)[:, None]
            ye = jnp.where(here, ye * s_w[:, None].astype(ye.dtype), 0)
            toks = s_tok
        out = jnp.zeros((N, d), ye.dtype).at[toks].add(ye)
    if cfg.n_shared_experts and "shared" in p:
        # shared_scale compensates for replication across axes the caller
        # will psum over (resident-EP mode)
        with jax.named_scope("moe_shared"):
            out = out + shared_scale * L.apply_mlp(p["shared"], x,
                                                   cfg.mlp_type)
    return out, aux, counts


def moe_local(p: Params, x: jax.Array, cfg: ModelConfig, *,
              e_first: int | jax.Array = 0,
              n_local: int | None = None,
              shared_scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """:func:`moe_local_counted` without its counts: (partial_out, aux)."""
    out, aux, _ = moe_local_counted(p, x, cfg, e_first=e_first,
                                    n_local=n_local,
                                    shared_scale=shared_scale)
    return out, aux


def ep_capable(cfg: ModelConfig, axis_size: int) -> bool:
    return cfg.n_experts % axis_size == 0 and cfg.n_experts >= axis_size


def moe_share(p: Params, x: jax.Array, cfg: ModelConfig,
              valid: jax.Array | None = None):
    """One chip's share of the layer outside a mesh: x [B, T, d] routes
    over all ``n_experts``; the held experts (shard ``expert_shard``) and
    the shared expert give the output.  Returns (out, aux, counts) as
    :func:`moe_local_counted`; ``valid`` ([B, T] bool) marks the rows that
    count."""
    B, T, d = x.shape
    n = cfg.experts_held
    out, aux, counts = moe_local_counted(
        p, x.reshape(B * T, d), cfg, e_first=cfg.expert_shard * n,
        n_local=n, valid=None if valid is None else valid.reshape(-1))
    return out.reshape(B, T, d).astype(x.dtype), aux, counts


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig,
              axis_name: str | None = None,
              reduce_fn=None) -> tuple[jax.Array, jax.Array]:
    """MoE over [B, T, d].  Inside shard_map pass ``axis_name='model'``;
    expert weights must already be the local shard (see moe_local).
    ``reduce_fn`` selects the combine collective (ring psum vs H-tree)."""
    if axis_name is None:
        out, aux, _ = moe_share(p, x, cfg)
        return out, aux
    if cfg.experts_held != cfg.n_experts:
        raise NotImplementedError(
            "a mesh shards the whole expert stack; a config that holds a "
            "share of the experts runs outside one")
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
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
