"""Intra-chunk SSD as a Pallas TPU kernel.

Grid: (batch*chunks, head-blocks).  Each step holds one chunk of one head
block in VMEM: the quadratic-within-chunk attention-like kernel
(C·Bᵀ ∘ decay) plus the chunk-state emission, everything fused — the
decay matrix, masked scores, and xdt never round-trip to HBM (they are the
dominant traffic of the pure-jnp path).  Head dim / state dim are
MXU-friendly (64/128); Q (chunk) is the sequential-friendly axis.

The inter-chunk recurrence stays a lax.scan on the host graph (it is
O(T/Q) and bandwidth-trivial); this kernel is the compute hot-spot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BLOCK_H = 4          # heads per grid step


def _kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, h_ref,
            y_ref, s_ref, dec_ref):
    x = x_ref[...].astype(jnp.float32)            # [Q,Hb,dh]
    B = b_ref[...].astype(jnp.float32)            # [Q,Hb,S]
    C = c_ref[...].astype(jnp.float32)
    dt = dt_ref[...].astype(jnp.float32)          # [Q,Hb]
    A = a_ref[...].astype(jnp.float32)            # [Hb]
    D = d_ref[...].astype(jnp.float32)
    h_in = h_ref[...].astype(jnp.float32)         # [Hb,dh,S]

    Q = x.shape[0]
    la = dt * A[None, :]
    cs = jnp.cumsum(la, axis=0)                   # [Q,Hb]
    xdt = x * dt[..., None]
    Ldec = jnp.exp(cs[:, None, :] - cs[None, :, :])
    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    Ldec = jnp.where((ik <= iq)[..., None], Ldec, 0.0)
    scores = jnp.einsum("qhs,khs->qkh", C, B,
                        preferred_element_type=jnp.float32) * Ldec
    y = jnp.einsum("qkh,khd->qhd", scores, xdt,
                   preferred_element_type=jnp.float32)
    y = y + jnp.einsum("qhs,hds->qhd", C * jnp.exp(cs)[..., None], h_in,
                       preferred_element_type=jnp.float32)
    y = y + D[None, :, None] * x
    decay_end = jnp.exp(cs[-1:, :] - cs)
    s_out = jnp.einsum("khs,khd->hds", B * decay_end[..., None], xdt,
                       preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    s_ref[...] = s_out.astype(s_ref.dtype)
    dec_ref[...] = jnp.exp(cs[-1, :]).astype(dec_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def ssd_chunk_pallas(x, B, C, dt, A, D, h_in, *, bh: int = BLOCK_H,
                     interpret: bool | None = None):
    """x: [N,Q,H,dh]; B,C: [N,Q,H,S]; dt: [N,Q,H]; A,D: [H]; h_in: [N,H,dh,S]
    -> (y [N,Q,H,dh], S_out [N,H,dh,S], decay [N,H]).  N = batch*chunks."""
    N, Q, H, dh = x.shape
    S = B.shape[-1]
    bh = min(bh, H)
    n_h = pl.cdiv(H, bh)
    return pl.pallas_call(
        _kernel,
        grid=(N, n_h),
        in_specs=[
            pl.BlockSpec((None, Q, bh, dh), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((None, Q, bh, S), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((None, Q, bh, S), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((None, Q, bh), lambda n, h: (n, 0, h)),
            pl.BlockSpec((bh,), lambda n, h: (h,)),
            pl.BlockSpec((bh,), lambda n, h: (h,)),
            pl.BlockSpec((None, bh, dh, S), lambda n, h: (n, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, Q, bh, dh), lambda n, h: (n, 0, h, 0)),
            pl.BlockSpec((None, bh, dh, S), lambda n, h: (n, h, 0, 0)),
            pl.BlockSpec((None, bh), lambda n, h: (n, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Q, H, dh), jnp.float32),
            jax.ShapeDtypeStruct((N, H, dh, S), jnp.float32),
            jax.ShapeDtypeStruct((N, H), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(x, B, C, dt, A, D, h_in)
