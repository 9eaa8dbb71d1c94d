"""Plain float32 reference forward of the DeepSeek-V3 stack the configs name.

Written from the configuration's widths and the published layer equations
(DeepSeek-V3 report, arXiv:2412.19437, and the model's config.json), with
nothing taken from the program:

- pre-norm RMSNorm (the config's eps) with a gain, also on the query and
  key/value latents;
- MLA in its unabsorbed form: per-head keys and values expanded from the
  normed latent through ``wkv_b``, queries through ``wq_a``/``wq_b``, a
  shared 64-dim rotary key; YaRN rotary frequencies (rotate-half over the
  two halves of the rope dims) and the softmax scale
  ``mscale(factor, mscale_all_dim)^2 / sqrt(qk_nope + qk_rope)``; causal
  softmax over every key row, computed a block of heads at a time;
- SwiGLU dense layers first, then MoE layers: sigmoid scores, selection on
  scores plus the correction bias among the experts of the best
  ``moe_topk_group`` groups (a group scores its two best biased scores),
  top ``n_experts_active`` of those, weights the unbiased scores of the
  chosen normalised over them times ``moe_routed_scaling``; the output is
  the shared expert plus the weighted outputs of the experts this chip
  holds (the configuration's shard), each computed densely over all tokens;
- a final RMSNorm and the untied head over the vocabulary slice.

Weights come from ``bench.weights.deepseek_v3`` and the run seed.  Every
product runs in float32 at ``Precision.HIGHEST``.  Layer by layer over
whole sequences, so it fits beside nothing else on the chip.

``control=True`` adds the int4 control: the same forward with every linear
of the layers (attention, dense, shared and routed experts; not the
router) rounded to symmetric per-output-channel int4.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.dense_decoder import _int4
from bench.weights import deepseek_v3 as W

HI = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 16          # heads whose [T, T] scores are held at once


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def yarn(m: dict):
    """(inverse frequencies [dr/2], softmax scale) from the config."""
    dr, theta = m["qk_rope_head_dim"], m["rope_theta"]
    base = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    scale = 1.0 / math.sqrt(m["qk_nope_head_dim"] + dr)
    factor = m["yarn_factor"]
    if not factor:
        return base.astype(np.float32), scale
    orig = m["yarn_original_max_pos"]

    def dim_at(rot):
        return dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_at(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(dim_at(m["yarn_beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    freqs = base / factor * ramp + base * (1 - ramp)
    if m["yarn_mscale_all_dim"]:
        scale *= (0.1 * m["yarn_mscale_all_dim"] * math.log(factor) + 1) ** 2
    return freqs.astype(np.float32), scale


def _rope(x, freqs):
    """x: [T, H, D] at positions 0..T-1, rotate-half."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(a, w, m):
    t = a.shape[0]
    h, eps = m["n_heads"], m["norm_eps"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    r = m["kv_lora_rank"]
    freqs, scale = yarn(m)
    q = _mm(_rms(_mm(a, w["wq_a"]), w["q_norm"], eps), w["wq_b"])
    q = q.reshape(t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], freqs)], -1)
    kv_a = _mm(a, w["wkv_a"])
    kv = _mm(_rms(kv_a[:, :r], w["kv_norm"], eps), w["wkv_b"])
    kv = kv.reshape(t, h, dn + dv)
    k_rope = _rope(kv_a[:, None, r:], freqs)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (t, h, dr))],
                        -1)
    v = kv[..., dn:]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def block(qkv):
        qb, kb, vb = qkv                                   # [hb, T, D]
        s = jnp.einsum("hqd,hkd->hqk", qb, kb, precision=HI) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, vb, precision=HI)

    hb = math.gcd(h, HEAD_BLOCK)

    def heads(x):
        return x.transpose(1, 0, 2).reshape(h // hb, hb, t, -1)

    o = jax.lax.map(block, (heads(q), heads(k), heads(v)))
    o = o.reshape(h, t, dv).transpose(1, 0, 2).reshape(t, h * dv)
    return _mm(o, w["wo"])


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def select(b, w, m):
    """Routed experts of each row of ``b`` [T, d]: (ids [T, k] best
    first, weights [T, k])."""
    t, n = b.shape[0], m["n_experts"]
    g, k = m["moe_n_group"], m["n_experts_active"]
    s = jax.nn.sigmoid(_mm(b, w["router"]))
    biased = s + w["router_bias"] if m["moe_router_bias"] else s
    per = biased.reshape(t, g, n // g)
    group = -jnp.sort(-per, axis=-1)[..., :2].sum(-1)           # [T, g]
    rank = jnp.argsort(jnp.argsort(-group, axis=-1), axis=-1)   # 0 = best
    allowed = jnp.repeat(rank < m["moe_topk_group"], n // g, axis=-1)
    ids = jnp.argsort(-jnp.where(allowed, biased, -jnp.inf), axis=-1)[:, :k]
    weights = jnp.take_along_axis(s, ids, -1)
    if m["moe_norm_topk"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return ids, weights * m["moe_routed_scaling"]


def _moe(b, w, experts, m):
    ids, weights = select(b, w, m)
    out = _swiglu(b, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e, ew in zip(W.held_ids(m), experts):
        g = jnp.sum(jnp.where(ids == e, weights, 0.0), -1)      # [T]
        out = out + g[:, None] * _swiglu(b, ew["w_gate"], ew["w_up"],
                                         ew["w_down"])
    return out


@functools.partial(jax.jit, static_argnames=("m", "moe", "int4"))
def _layer_weights(key, i, m, moe, int4):
    m = dict(m)
    w = {k: v.astype(jnp.float32)
         for k, v in W.layer_weights(key, m, i, moe).items()}
    experts = [{k: v.astype(jnp.float32)
                for k, v in W.expert_weights(key, m, i, e).items()}
               for e in (W.held_ids(m) if moe else ())]
    if int4:
        lin = W.ATTN + (W.SHARED if moe else W.MLP)
        w.update({k: _int4(w[k]) for k in lin})
        experts = [{k: _int4(v) for k, v in ew.items()} for ew in experts]
    return w, experts


@functools.partial(jax.jit, static_argnames=("m", "moe"))
def _layer(x, w, experts, m, moe):
    m = dict(m)
    eps = m["norm_eps"]
    x = x + _attention(_rms(x, w["ln1"], eps), w, m)
    b = _rms(x, w["ln2"], eps)
    if moe:
        return x + _moe(b, w, experts, m)
    return x + _swiglu(b, w["w_gate"], w["w_up"], w["w_down"])


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, m):
    return W.embed(key, dict(m)).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _seq_gaps(key, x, xc, tokens, m, control):
    """Gaps at every position of one padded sequence: row p is read
    against token p + 1."""
    m = dict(m)
    eps = m["norm_eps"]
    gain, out = W.final_norm(key, m), W.head(key, m).astype(jnp.float32)
    ref = _mm(_rms(x[:-1], gain, eps), out)
    best = ref.max(-1)
    at = lambda tok: jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    served = best - at(tokens[1:])
    if not control:
        return served, served
    ctrl = _mm(_rms(xc[:-1], gain, eps), out)
    return served, best - at(jnp.argmax(ctrl, -1))


KEYS = ("n_layers", "d_model", "n_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff",
        "vocab_size", "n_experts", "n_experts_active", "n_shared_experts",
        "moe_d_ff", "first_dense_layers", "moe_router_bias", "moe_n_group",
        "moe_topk_group", "moe_norm_topk", "moe_routed_scaling",
        "n_experts_held", "expert_shard", "rope_theta", "yarn_factor",
        "yarn_original_max_pos", "yarn_beta_fast", "yarn_beta_slow",
        "yarn_mscale_all_dim", "norm_eps")


def _static(m: dict) -> tuple:
    return tuple((k, m[k]) for k in KEYS)


def gaps(m: dict, weight_seed: int, seqs: list[tuple[list[int], int]],
         t_pad: int, control: bool = False) -> list[dict]:
    """For each ``(tokens, n_prompt)`` (prompt then the served tokens):
    ``served``: how far each served token's logit lies below the
    reference's best at its position; with ``control``, also ``control``:
    the same gap for the token the int4 control puts first."""
    ms = _static(m)
    key = jax.random.key(weight_seed)
    toks = [np.zeros((t_pad,), np.int32) for _ in seqs]
    for t, (tokens, _) in zip(toks, seqs):
        if len(tokens) > t_pad:
            raise ValueError(f"sequence of {len(tokens)} > {t_pad}")
        t[:len(tokens)] = tokens
    sides = (False, True) if control else (False,)
    xs = {s: [_embed(key, jnp.asarray(t), ms) for t in toks] for s in sides}
    for i in range(m["n_layers"]):
        moe = W.is_moe(m, i)
        for s in sides:
            w, experts = _layer_weights(key, jnp.int32(i), ms, moe, s)
            xs[s] = [_layer(x, w, experts, ms, moe) for x in xs[s]]
            del w, experts
    out = []
    for j, (tokens, n_prompt) in enumerate(seqs):
        xc = xs[True][j] if control else xs[False][j]
        g_served, g_ctrl = _seq_gaps(key, xs[False][j], xc,
                                     jnp.asarray(toks[j]), ms, control)
        sl = slice(n_prompt - 1, len(tokens) - 1)
        out.append({"served": np.asarray(g_served)[sl],
                    "control": np.asarray(g_ctrl)[sl] if control else None})
    return out


def logits(m: dict, weight_seed: int, tokens: list[int]) -> np.ndarray:
    """The reference's logits [T, V] at every position of one sequence."""
    ms = _static(m)
    key = jax.random.key(weight_seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), ms)
    for i in range(m["n_layers"]):
        moe = W.is_moe(m, i)
        w, experts = _layer_weights(key, jnp.int32(i), ms, moe, False)
        x = _layer(x, w, experts, ms, moe)
    gain = W.final_norm(key, m)
    return np.asarray(_mm(_rms(x, gain, m["norm_eps"]),
                          W.head(key, m).astype(jnp.float32)))
