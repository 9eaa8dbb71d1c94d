"""Seeded random weights of a dense decoder, made by the benchmark (the
weight builder that a configuration's ``family_module`` names).

The same per-layer function feeds both sides: :func:`program_params` stacks
it over the layers in one jitted call (the serving layout the program
takes), and the reference regenerates one layer at a time.  Each tensor
comes from its own folded key and is built from random bits by exact
arithmetic only (a sum of four random bytes, one multiplication, one
rounding to bfloat16), so however XLA fuses the two programs they draw the
same bits: no transcendental, no contractible multiply-add.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# fold_in tags for the tensors outside the layer stack
_EMBED, _HEAD, _LN_F, _LAYERS = 0, 1, 2, 3


def linear_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """[in, out] of each linear of one layer, from the config's widths."""
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    h, g = m["n_heads"], m["n_kv_heads"]
    return {"wq": (d, h * hd), "wk": (d, g * hd), "wv": (d, g * hd),
            "wo": (h * hd, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}


# a sum of four uniform bytes, centred: near-normal (Irwin-Hall), integer
_IH_MEAN, _IH_STD = 510, math.sqrt(4 * (256**2 - 1) / 12)


def _normal(key, shape, scale):
    """Near-normal bf16 values of standard deviation ``scale``."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    s = sum((bits >> (8 * k)) & 0xFF for k in range(4)).astype(jnp.int32)
    c = np.float32(scale / _IH_STD)
    return ((s - _IH_MEAN).astype(jnp.float32) * c).astype(jnp.bfloat16)


def _norm_scale(key, d):
    """RMSNorm gains uniform in [0.75, 1.25), exact in float32, so a norm
    that drops its gain shows."""
    bits = jax.random.bits(key, (d,), jnp.uint32) >> 23
    return 0.75 + bits.astype(jnp.float32) * np.float32(2.0**-10)


def layer_weights(key, m: dict, i) -> dict:
    """Layer ``i`` (may be traced): bf16 linears scaled 1/sqrt(fan_in),
    float32 norm gains."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LAYERS), i)
    out = {name: _normal(jax.random.fold_in(k, j), shape,
                         1.0 / math.sqrt(shape[0]))
           for j, (name, shape) in enumerate(linear_shapes(m).items())}
    out["ln1"] = _norm_scale(jax.random.fold_in(k, 100), m["d_model"])
    out["ln2"] = _norm_scale(jax.random.fold_in(k, 101), m["d_model"])
    return out


def embed(key, m: dict):
    return _normal(jax.random.fold_in(key, _EMBED),
                   (m["vocab_size"], m["d_model"]), 0.02)


def head(key, m: dict):
    """[d, V] output head; a tied config reads the embedding instead."""
    if m["tie_embeddings"]:
        return embed(key, m).T
    return _normal(jax.random.fold_in(key, _HEAD),
                   (m["d_model"], m["vocab_size"]),
                   1.0 / math.sqrt(m["d_model"]))


def final_norm(key, m: dict):
    return _norm_scale(jax.random.fold_in(key, _LN_F), m["d_model"])


def program_params(key, m: dict) -> dict:
    """The whole parameter tree in the program's layout (one group of
    ``n_layers`` identical dense layers).  Call under ``jax.jit``."""
    layers = jax.vmap(lambda i: layer_weights(key, m, i))(
        jnp.arange(m["n_layers"]))
    slot = {"ln1": {"scale": layers["ln1"]},
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": layers["ln2"]},
            "mlp": {k: layers[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": {"w": embed(key, m)}, "ln_f": {"scale": final_norm(key, m)},
         "groups": ((slot,),)}
    if not m["tie_embeddings"]:
        p["lm_head"] = {"w": head(key, m)}
    return p
