"""Beyond-paper optimized W8A8 kernel: single-pass int8 MXU matmul.

Where the paper's array is bit-serial (8 sequential input-bit passes, Eq. 3's
``x B_input`` latency factor), the TPU MXU consumes full int8 operands in one
systolic pass.  Same integer math, 8x fewer passes — this is the
hardware-adaptation headline (DESIGN.md Sec. 3).  Tiles are MXU-aligned
(multiples of 128); K-accumulation uses a VMEM scratch; the dequant epilogue
fuses the per-token and per-channel scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BLOCK_M = 128
BLOCK_K = 512
BLOCK_N = 256


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 straight into the MXU (s8 x s8 -> s32); Mosaic rejects a
    # dot over int32 operands
    acc_ref[...] += jax.lax.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs_ref[...] * ws_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "out_dtype",
                                             "interpret"))
def int8_matmul_pallas(x_q, x_s, w_q, w_s, *, bm: int = BLOCK_M,
                       bk: int = BLOCK_K, bn: int = BLOCK_N,
                       out_dtype=jnp.float32, interpret: bool | None = None):
    M, K = x_q.shape
    N = w_q.shape[1]
    n_m, n_n, n_k = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    ws2 = w_s.reshape(1, N)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bm, 1), lambda m, n, k: (m, 0)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x_q, w_q, x_s, ws2)
