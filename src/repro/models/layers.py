"""Shared neural building blocks (pure JAX, pytree params).

Every linear layer is a dict ``{"w": [in, out]}`` (bf16/f32 training path) or
its quantized "QLC-region" form ``{"w_q", "w_s", ("smooth")}`` produced by
:func:`repro.core.quant.make_quantized_linear`.  ``apply_linear`` dispatches
on the param form and the execution backend, so the same model code runs the
bf16 training path, the W8A8 reference path, or the Pallas PIM kernels.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initialisation helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    w = jax.random.normal(key, (d_in, d_out), dtype) * scale
    return {"w": w}


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"w": jax.random.normal(key, (vocab, d), dtype) * 0.02}


# ---------------------------------------------------------------------------
# linear dispatch (dense | quantized-ref | pallas kernels)
# ---------------------------------------------------------------------------
def apply_linear(p: Params, x: jax.Array, backend: str = "dense") -> jax.Array:
    """x: [..., in] -> [..., out]."""
    if "w_q" in p:
        lin = quant.QuantizedLinear(w_q=p["w_q"], w_scale=p["w_s"],
                                    smooth=p.get("smooth"))
        if lin.smooth is not None:
            x = x * (1.0 / lin.smooth)
        x_q, x_s = quant.quantize_activation(x)
        if backend == "pim_bitserial":
            from repro.kernels.pim_mvm import ops as pim_ops
            return pim_ops.pim_mvm(x_q, x_s, lin, out_dtype=x.dtype)
        if backend == "fused_int8":
            from repro.kernels.int8_matmul import ops as mm_ops
            return mm_ops.int8_matmul(x_q, x_s, lin, out_dtype=x.dtype)
        return quant.int8_matmul_ref(x_q, x_s, lin, out_dtype=x.dtype)
    return jnp.einsum("...i,io->...o", x, p["w"].astype(x.dtype))


def quantize_linear_params(p: Params, act_amax: jax.Array | None = None) -> Params:
    lin = quant.make_quantized_linear(p["w"].astype(jnp.float32), act_amax)
    out = {"w_q": lin.w_q, "w_s": lin.w_scale}
    if lin.smooth is not None:
        out["smooth"] = lin.smooth
    return out


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def norm_init(d: int, norm_type: str = "rmsnorm"):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Controller op (fp32 'ARM-core' path): always computed in fp32."""
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary / sinusoidal positions
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 * mscale * ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (DeepSeek-V3's rotary embedding): the
    plain ``theta^(-2i/d)`` below the ramp, the same divided by ``factor``
    above it, mixed linearly between the pair indices at which a frequency
    turns ``beta_fast`` and ``beta_slow`` times over ``original``
    positions."""
    i = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    extra = (1.0 / theta ** i).astype(np.float32)
    inter = (extra / factor).astype(np.float32)

    def dim_at(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs=None) -> jax.Array:
    """x: [B, T, H, D]; positions: [B, T] (or [T]).  ``freqs`` ([D/2],
    optional) replaces the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if freqs is None else freqs   # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope_spmd(x: jax.Array, positions: jax.Array, theta: float,
                    freqs=None) -> jax.Array:
    """Partition-safe RoPE: same rotation as :func:`apply_rope` but written
    as a per-position [D, D] rotation *contraction* instead of rotate-half's
    split+concat.  XLA's SPMD partitioner mis-partitions the concat when
    ``x`` arrives as a deferred partial sum (observed on jax 0.4.x: the
    partials are gathered without being reduced, scaling the result by the
    sharded axis size); a contraction forces the reduction, so the sharded
    chunked-prefill path routes RoPE through here.  O(D^2) per position vs
    O(D) — negligible beside attention, and only paid under a mesh."""
    d = x.shape[-1]
    half = d // 2
    freqs = rope_freqs(d, theta) if freqs is None else freqs   # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs     # [B, T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    i = jnp.arange(half)
    rot = jnp.zeros((*ang.shape[:-1], d, d), jnp.float32)      # [B, T, D, D]
    rot = (rot.at[..., i, i].set(cos)
              .at[..., half + i, i].set(-sin)
              .at[..., i, half + i].set(sin)
              .at[..., half + i, half + i].set(cos))
    out = jnp.einsum("...thd,...tde->...the", x.astype(jnp.float32), rot)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int, offset=0) -> jax.Array:
    pos = (jnp.arange(seq) + offset)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# MLPs: swiglu | gelu | relu2 (squared ReLU, Nemotron-4)
# ---------------------------------------------------------------------------
def mlp_init(key, d: int, ff: int, mlp_type: str, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d, ff, dtype)["w"],
         "w_down": dense_init(ks[1], ff, d, dtype)["w"]}
    if mlp_type == "swiglu":
        p["w_gate"] = dense_init(ks[2], d, ff, dtype)["w"]
    return p


def apply_mlp(p: Params, x: jax.Array, mlp_type: str, backend: str = "dense") -> jax.Array:
    up = apply_linear(_lin(p, "w_up"), x, backend)
    if mlp_type == "swiglu":
        gate = apply_linear(_lin(p, "w_gate"), x, backend)
        h = jax.nn.silu(gate) * up
    elif mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:  # gelu
        h = jax.nn.gelu(up)
    return apply_linear(_lin(p, "w_down"), h, backend)


def _lin(p: Params, name: str) -> Params:
    """Fetch sub-linear ``name`` whether dense or quantized."""
    if name + "_q" in p:
        out = {"w_q": p[name + "_q"], "w_s": p[name + "_s"]}
        if name + "_smooth" in p:
            out["smooth"] = p[name + "_smooth"]
        return out
    return {"w": p[name]}


def quantize_named(p: Params, names: list[str]) -> Params:
    """Replace the listed [in,out] weights with their W8A8 'QLC' form."""
    out = dict(p)
    for n in names:
        if n not in p:
            continue
        q = quantize_linear_params({"w": p[n]})
        del out[n]
        out[n + "_q"], out[n + "_s"] = q["w_q"], q["w_s"]
    return out
