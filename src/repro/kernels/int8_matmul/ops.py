"""jit'd wrapper for the fused W8A8 matmul kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.int8_matmul import kernel as K


def int8_matmul(x_q: jax.Array, x_s: jax.Array, lin: quant.QuantizedLinear,
                out_dtype=jnp.float32,
                interpret: bool | None = None) -> jax.Array:
    lead = x_q.shape[:-1]
    Kdim = x_q.shape[-1]
    x2 = x_q.reshape(-1, Kdim)
    s2 = x_s.reshape(-1, 1)
    M = x2.shape[0]
    N = lin.w_q.shape[1]
    bm = min(K.BLOCK_M, max(8, M))
    pad_m = (-M) % bm
    pad_k = (-Kdim) % K.BLOCK_K
    pad_n = (-N) % 128
    w_q, w_s = lin.w_q, lin.w_scale
    if pad_m or pad_k:
        x2 = jnp.pad(x2, ((0, pad_m), (0, pad_k)))
        s2 = jnp.pad(s2, ((0, pad_m), (0, 0)))
    if pad_k or pad_n:
        w_q = jnp.pad(w_q, ((0, pad_k), (0, pad_n)))
        w_s = jnp.pad(w_s, (0, pad_n))
    bn = min(K.BLOCK_N, N + pad_n)
    out = K.int8_matmul_pallas(x2, s2, w_q, w_s, bm=bm, bn=bn,
                               out_dtype=jnp.float32, interpret=interpret)
    return out[:M, :N].reshape(*lead, N).astype(out_dtype)
