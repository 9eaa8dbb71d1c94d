"""Plain float32 reference forward of the dense decoder the configs name.

Written from the configuration's widths and the published layer equations,
with nothing taken from the program: pre-norm RMSNorm (eps 1e-5) with a
gain, rotary positions (rotate-half over the two halves of each head,
theta from the config), causal softmax attention with ``n_heads // n_kv_heads``
query heads per key/value head, a SwiGLU MLP, a final RMSNorm and an output
head (tied to the embedding where the config says so).  Weights come from
``bench.weights`` and the run seed, as the program's do.  Every product runs
in float32 at ``Precision.HIGHEST``.

Runs layer by layer over whole sequences, so it fits beside nothing else on
the chip: one layer's float32 weights plus each sequence's [T, d] state.

``int4=True`` is the control: the same forward with every linear of the
layers rounded to symmetric per-output-channel int4, the step below the int8
the program decodes with.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dense_decoder as W

HI = jax.lax.Precision.HIGHEST


def _rms(x, gain, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: [T, H, D] at positions 0..T-1."""
    t, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _int4(w):
    """Symmetric int4 per output channel ([in, out], contraction axis 0)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-8) / 7.0
    return jnp.clip(jnp.round(w / s), -7, 7) * s


@functools.partial(jax.jit, static_argnames=("m", "int4"))
def _layer_weights(key, i, m, int4):
    w = W.layer_weights(key, dict(m), i)
    out = {k: v.astype(jnp.float32) for k, v in w.items()}
    if int4:
        out.update({k: _int4(out[k]) for k in W.LINEARS})
    return out


@functools.partial(jax.jit, static_argnames=("m",))
def _layer(x, w, m):
    m = dict(m)
    t = x.shape[0]
    h, g, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = _rms(x, w["ln1"])
    q = jnp.matmul(a, w["wq"], precision=HI).reshape(t, h, hd)
    k = jnp.matmul(a, w["wk"], precision=HI).reshape(t, g, hd)
    v = jnp.matmul(a, w["wv"], precision=HI).reshape(t, g, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h * hd)
    x = x + jnp.matmul(o, w["wo"], precision=HI)
    b = _rms(x, w["ln2"])
    gate = jnp.matmul(b, w["w_gate"], precision=HI)
    up = jnp.matmul(b, w["w_up"], precision=HI)
    return x + jnp.matmul(jax.nn.silu(gate) * up, w["w_down"], precision=HI)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, m):
    return W.embed(key, dict(m)).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _seq_gaps(key, x, xc, tokens, m, control):
    """Gaps at every position of one padded sequence: row p is read
    against token p + 1."""
    m = dict(m)
    gain, out = W.final_norm(key, m), W.head(key, m).astype(jnp.float32)
    ref = jnp.matmul(_rms(x[:-1], gain), out, precision=HI)
    best = ref.max(-1)
    at = lambda tok: jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    served = best - at(tokens[1:])
    if not control:
        return served, served
    ctrl = jnp.matmul(_rms(xc[:-1], gain), out, precision=HI)
    return served, best - at(jnp.argmax(ctrl, -1))


def _static(m: dict) -> tuple:
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "tie_embeddings", "rope_theta")
    return tuple((k, m[k]) for k in keys)


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
        for s in sides:
            w = _layer_weights(key, jnp.int32(i), ms, s)
            xs[s] = [_layer(x, w, ms) for x in xs[s]]
            del w
    out = []
    for j, (tokens, n_prompt) in enumerate(seqs):
        xc = xs[True][j] if control else xs[False][j]
        g_served, g_ctrl = _seq_gaps(key, xs[False][j], xc,
                                     jnp.asarray(toks[j]), ms, control)
        # row p predicts token p + 1: the served tokens are
        # tokens[n_prompt:], read at rows n_prompt - 1 .. len - 2
        sl = slice(n_prompt - 1, len(tokens) - 1)
        out.append({"served": np.asarray(g_served)[sl],
                    "control": np.asarray(g_ctrl)[sl] if control else None})
    return out
