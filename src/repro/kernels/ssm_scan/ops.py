"""jit'd wrapper: full chunked SSD forward using the Pallas chunk kernel for
the intra-chunk quadratic part + a host-graph scan for the inter-chunk
recurrence."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.kernel import ssd_chunk_pallas


def ssd_forward(x, B, C, dt, A, D, *, chunk: int = 128, h0=None,
                interpret: bool | None = None):
    """x: [Bt,T,H,dh]; B,C: [Bt,T,H,S]; dt: [Bt,T,H]; A,D: [H].
    Returns (y [Bt,T,H,dh], h_last [Bt,H,dh,S])."""
    Bt, T, H, dh = x.shape
    S = B.shape[-1]
    Q = min(chunk, T)
    nc = math.ceil(T / Q)
    pad = nc * Q - T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    xc = x.reshape(Bt, nc, Q, H, dh)
    Bc = B.reshape(Bt, nc, Q, H, S)
    Cc = C.reshape(Bt, nc, Q, H, S)
    dtc = dt.reshape(Bt, nc, Q, H)
    h = h0 if h0 is not None else jnp.zeros((Bt, H, dh, S), jnp.float32)

    # sequential over chunks (the recurrence); kernel over (batch, heads)
    def step(h, inp):
        xq, bq, cq, dq = inp                       # [Bt,Q,H,*]
        y, s_out, dec = ssd_chunk_pallas(xq, bq, cq, dq, A, D, h,
                                         interpret=interpret)
        h_new = dec[:, :, None, None] * h + s_out
        return h_new, y

    h_last, ys = jax.lax.scan(
        step, h, (xc.transpose(1, 0, 2, 3, 4), Bc.transpose(1, 0, 2, 3, 4),
                  Cc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bt, nc * Q, H, dh)[:, :T]
    return y, h_last
