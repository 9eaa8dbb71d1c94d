"""Offline weight quantization: bf16 checkpoint -> W8A8 'QLC-region' params.

This is the paper's deployment step: static weights move into the dense
flash (int8, nibble-packable) while controller-op parameters (norms, router,
SSM B/C/dt, embeddings) stay in floating point.  2-D linears become
(w_q, w_s) pairs consumed by `layers.apply_linear` (ref / fused_int8 /
pim_bitserial backends); 3-D expert stacks become weight-only int8."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import quant

# 2-D [in, out] weights that become full W8A8 PIM linears
_SMVM_2D = {"wq", "wk", "wv", "wo", "wq_a", "wq_b", "wkv_a", "wkv_b",
            "w_up", "w_gate", "w_down", "w_z", "w_x", "out_proj", "w"}
# 3-D [E, in, out] expert stacks -> weight-only int8
_SMVM_3D = {"w_up", "w_gate", "w_down"}
# kept in float (controller ops / sensitive small projections)
_KEEP = {"router", "w_B", "w_C", "w_dt", "conv_x", "conv_B", "conv_C"}


# Each [..., in, out] leaf quantizes per output channel through two jitted
# passes, so the f32 temporaries fuse away instead of materialising (a
# stacked [L, 3072, 8192] leaf would otherwise hold several 3 GB f32 copies
# at once).  The [..., out] scale between them is computed eagerly: under
# jit XLA turns the division by 127 into an inexact reciprocal multiply,
# and the scales must stay the eager path's bit for bit.
@functools.partial(jax.jit, static_argnames=("dtype",))
def _channel_amax(w: jax.Array, dtype) -> jax.Array:
    return jnp.max(jnp.abs(w.astype(dtype)), axis=-2)


@jax.jit
def _to_int8(w: jax.Array, scale: jax.Array) -> jax.Array:
    q = jnp.round(w.astype(scale.dtype) / scale[..., None, :])
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def _quantize(w: jax.Array, dtype):
    scale = jnp.maximum(_channel_amax(w, dtype), 1e-8) / quant.INT8_MAX
    return _to_int8(w, scale), scale.astype(jnp.float32)


def _quantize_2d(w: jax.Array):
    """W8A8 linear (or a layer stack of them): f32 scales, as
    :func:`repro.core.quant.make_quantized_linear`."""
    return _quantize(w, jnp.float32)


def _quantize_3d(w: jax.Array):
    """Weight-only int8 expert stack ``[..., E, in, out]``, scaled in the
    weights' own dtype."""
    return _quantize(w, w.dtype)


def quantize_tree(params: Any, quantize_embed: bool = False) -> Any:
    """Recursively replace sMVM weights by (name_q, name_s) pairs."""
    def rec_seq(seq, path):
        return type(seq)(
            rec(e, path) if isinstance(e, dict)
            else rec_seq(e, path) if isinstance(e, (tuple, list))
            else e for e in seq)

    def rec(node, path):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                if k == "embed" and not quantize_embed:
                    out[k] = v
                else:
                    out[k] = rec(v, path + [k])
            elif isinstance(v, (tuple, list)):
                out[k] = rec_seq(v, path + [k])
            elif hasattr(v, "ndim") and k in _KEEP:
                out[k] = v
            elif hasattr(v, "ndim") and v.ndim == 3 and k in _SMVM_3D:
                # stacked-over-layers 2D weight [L, in, out] vs expert stack:
                # experts live under a "moe" dict; layer stacks under groups
                if "moe" in path:
                    q, s = _quantize_3d(v)
                else:
                    q, s = _quantize_2d(v)
                out[k + "_q"], out[k + "_s"] = q, s
            elif hasattr(v, "ndim") and v.ndim == 4 and k in _SMVM_3D and "moe" in path:
                # stacked-over-layers expert stack [L, E, in, out]
                q, s = _quantize_3d(v)
                out[k + "_q"], out[k + "_s"] = q, s
            elif hasattr(v, "ndim") and v.ndim == 3 and k in _SMVM_2D:
                q, s = _quantize_2d(v)                      # [L, in, out]
                out[k + "_q"], out[k + "_s"] = q, s
            elif hasattr(v, "ndim") and v.ndim == 2 and k in _SMVM_2D and k != "w":
                q, s = _quantize_2d(v)
                out[k + "_q"], out[k + "_s"] = q, s
            else:
                out[k] = v
        return out
    return rec(params, [])


def quantized_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))
