"""Paper-faithful bit-serial QLC PIM MVM as a Pallas TPU kernel.

The grid tile mirrors the selected plane (Sec. III-B): each (m, n, k) step
consumes a ``u x tile_cols`` weight tile — u = 128 activated BLS rows,
tile_cols = N_col/4 = 512 ADC output columns — exactly one PIM plane op.
Inside the tile the kernel executes Eq. (2) literally: 8 input bit-planes,
two 4-bit weight nibble planes, shift-add accumulation in int32 (the SAR-ADC
+ shift-adder datapath), with the fp32 dequant epilogue on the final k step
(the RPU/controller side).

The k-grid dimension accumulates into a VMEM scratch accumulator, which is
the H-tree's in-network partial-sum role mapped onto the sequential TPU grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# PIM plane-op tile (Size A): 128 rows x 512 cols
BLOCK_M = 8
BLOCK_K = 128      # u: simultaneously activated BLSs
BLOCK_N = 512      # N_col / 4 (ADC columns)
BITS = 8


def _kernel(x_ref, hi_ref, lo_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
            n_k: int, bits: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32) & 0xFF           # two's-complement byte
    hi = hi_ref[...].astype(jnp.int32)
    lo = lo_ref[...].astype(jnp.int32)
    acc = acc_ref[...]
    for b in range(bits):                              # bit-serial input passes
        plane = (x >> b) & 1                           # BLS on/off per Eq. (2)
        hi_dp = jax.lax.dot(plane, hi,
                            preferred_element_type=jnp.int32)  # hi-nibble BL sum
        lo_dp = jax.lax.dot(plane, lo,
                            preferred_element_type=jnp.int32)  # lo-nibble BL sum
        weight = (1 << b) if b < bits - 1 else -(1 << b)       # sign bit
        acc = acc + weight * (16 * hi_dp + lo_dp)              # shift-adders
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _epilogue():                                   # controller dequant
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs_ref[...] * ws_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "bits",
                                             "out_dtype", "interpret"))
def pim_mvm_pallas(x_q: jax.Array, x_s: jax.Array, w_hi: jax.Array,
                   w_lo: jax.Array, w_s: jax.Array, *, bm: int = BLOCK_M,
                   bk: int = BLOCK_K, bn: int = BLOCK_N, bits: int = BITS,
                   out_dtype=jnp.float32,
                   interpret: bool | None = None) -> jax.Array:
    """x_q: [M, K] int8; x_s: [M, 1] f32; w_hi/w_lo: [K, N] int8 nibbles;
    w_s: [N] f32  ->  [M, N] out_dtype."""
    M, K = x_q.shape
    N = w_hi.shape[1]
    n_m, n_n, n_k = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    ws2 = w_s.reshape(1, N)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, bits=bits),
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
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
    )(x_q, w_hi, w_lo, x_s, ws2)
