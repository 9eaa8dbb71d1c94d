"""jit'd wrapper: QuantizedLinear -> bit-serial PIM Pallas kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.pim_mvm import kernel as K


def pim_mvm(x_q: jax.Array, x_s: jax.Array, lin: quant.QuantizedLinear,
            out_dtype=jnp.float32,
            interpret: bool | None = None) -> jax.Array:
    """x_q: [..., K] int8 with per-token scales x_s: [..., 1]."""
    lead = x_q.shape[:-1]
    Kdim = x_q.shape[-1]
    x2 = x_q.reshape(-1, Kdim)
    s2 = x_s.reshape(-1, 1)
    w_hi, w_lo = quant.pack_qlc(lin.w_q)
    M = x2.shape[0]
    pad_m = (-M) % K.BLOCK_M
    pad_k = (-Kdim) % K.BLOCK_K
    N = lin.w_q.shape[1]
    pad_n = (-N) % 128
    if pad_m or pad_k:
        x2 = jnp.pad(x2, ((0, pad_m), (0, pad_k)))
        s2 = jnp.pad(s2, ((0, pad_m), (0, 0)))
    if pad_k or pad_n:
        w_hi = jnp.pad(w_hi, ((0, pad_k), (0, pad_n)))
        w_lo = jnp.pad(w_lo, ((0, pad_k), (0, pad_n)))
    w_s = jnp.pad(lin.w_scale, (0, pad_n)) if pad_n else lin.w_scale
    bn = min(K.BLOCK_N, N + pad_n)
    out = K.pim_mvm_pallas(x2, s2, w_hi, w_lo, w_s, bn=bn,
                           out_dtype=jnp.float32, interpret=interpret)
    out = out[:M, :N]
    return out.reshape(*lead, N).astype(out_dtype)
