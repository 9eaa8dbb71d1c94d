"""Kernel microbenchmarks + BlockSpec tile-shape sweep.

The tile sweep is the TPU analog of the paper's plane-size DSE (Fig. 6):
block shape determines the claimed VMEM working set and MXU alignment.
CPU interpret-mode wall times are NOT TPU times; the *structural* outputs
(VMEM footprint per tile, passes over the weight) are the design signal.
"""
import jax
import jax.numpy as jnp

from repro.core import quant
from benchmarks.common import emit, time_fn


def _vmem_bytes(bm, bk, bn):
    return bm * bk + bk * bn * 2 + bm * bn * 4 + bm * bn * 4   # x, hi+lo, acc, out


def run():
    key = jax.random.key(0)
    M, K, N = 16, 1024, 2048
    x = jax.random.normal(key, (M, K))
    w = jax.random.normal(jax.random.key(1), (K, N)) * 0.3
    lin = quant.make_quantized_linear(w)
    x_q, x_s = quant.quantize_activation(x)

    from repro.kernels.int8_matmul.kernel import int8_matmul_pallas
    from repro.kernels.pim_mvm.kernel import pim_mvm_pallas
    hi, lo = quant.pack_qlc(lin.w_q)

    t = time_fn(lambda: quant.int8_matmul_ref(x_q, x_s, lin))
    emit("kernel/ref_int8_matmul", t, f"{M}x{K}x{N}")

    for bk, bn in [(128, 512), (256, 256), (512, 512), (128, 128)]:
        f = jax.jit(lambda xq, xs: pim_mvm_pallas(
            xq, xs, hi, lo, lin.w_scale, bm=8, bk=bk, bn=bn))
        t = time_fn(f, x_q, x_s)
        emit(f"kernel/pim_mvm_bk{bk}_bn{bn}", t,
             f"vmem_tile_B={_vmem_bytes(8, bk, bn)};passes=8bit-serial")
    for bk, bn in [(512, 256), (256, 256), (1024, 128)]:
        f = jax.jit(lambda xq, xs: int8_matmul_pallas(
            xq, xs, lin.w_q, lin.w_scale, bm=16, bk=bk, bn=bn))
        t = time_fn(f, x_q, x_s)
        emit(f"kernel/int8_mm_bk{bk}_bn{bn}", t,
             f"vmem_tile_B={_vmem_bytes(16, bk, bn)};passes=1")
    emit("kernel/bitserial_vs_fused_passes", 0.0,
         "paper array: 8 bit-serial passes (Eq.3 xB_input); MXU: 1 pass")
    run_decode_attn()
    run_verify_attn()
    run_ssm()


def run_decode_attn():
    """Flash-decoding kernel at short context lengths: the block-skip guard
    (`pl.when(s_idx * bs < max(limits))`) should make wall time track the
    *live* prefix, not cdiv(max_len, bs) — the structural signal is the
    live-block count per length."""
    import jax
    from repro.kernels.decode_attn import ops as da_ops
    key = jax.random.key(0)
    B, S, G, rep, D = 2, 2048, 2, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, G * rep, D))
    k = jax.random.normal(ks[1], (B, S, G, D))
    v = jax.random.normal(ks[2], (B, S, G, D))
    k_q, k_s = quant.quantize_kv(k)
    v_q, v_s = quant.quantize_kv(v)
    from repro.kernels.decode_attn.kernel import BLOCK_S
    for length in (3, 64, 512, 2048):
        ln = jnp.full((B,), length, jnp.int32)
        t = time_fn(lambda ln=ln: da_ops.decode_attention(
            q, k_q, k_s, v_q, v_s, ln))
        live = -(-length // BLOCK_S)
        total = -(-S // BLOCK_S)
        emit(f"kernel/decode_attn_S{S}_len{length}", t,
             f"live_blocks={live}/{total};bs={BLOCK_S}")


def run_verify_attn():
    """Speculative verify-window kernel (per-row ancestor bitmask) at a few
    window sizes, on a chain window of T-1 drafts.  One pass scores all T
    window rows against the live prefix, so the structural signal is
    rows-per-pass: T rows amortize the same K/V sweep a single decode row
    pays."""
    import numpy as np
    from repro.kernels.decode_attn import ops as da_ops
    from repro.kernels.decode_attn.kernel import BLOCK_S
    from repro.serve.drafter import chain_parents, tree_depths_ancestors
    key = jax.random.key(0)
    B, S, G, rep, D = 2, 2048, 2, 2, 64
    length = 512
    for T in (4, 8):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, T, G * rep, D))
        k = jax.random.normal(ks[1], (B, S, G, D))
        v = jax.random.normal(ks[2], (B, S, G, D))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        pos = jnp.full((B,), length, jnp.int32)
        live = -(-(length + T) // BLOCK_S)
        _, anc_l = tree_depths_ancestors(chain_parents(T - 1))
        anc = jnp.asarray(np.tile(np.asarray(anc_l, np.int32), (B, 1)))
        t = time_fn(lambda q=q, k_q=k_q, k_s=k_s, v_q=v_q, v_s=v_s,
                    pos=pos, anc=anc: da_ops.verify_attention_tree(
                        q, k_q, k_s, v_q, v_s, pos, anc))
        emit(f"kernel/verify_attn_tree_T{T}_len{length}", t,
             f"rows_per_pass={T};live_blocks={live};ancestor_mask=int32")


def run_ssm():
    """SSD chunk-kernel sweep (mamba2/jamba compute hot-spot)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ssm_scan.kernel import ssd_chunk_pallas
    key = jax.random.key(0)
    for Q, H, dh, S in [(64, 8, 64, 32), (128, 8, 64, 64)]:
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (2, Q, H, dh))
        B = jax.random.normal(ks[1], (2, Q, H, S))
        C = jax.random.normal(ks[2], (2, Q, H, S))
        dt = jax.nn.softplus(jax.random.normal(ks[3], (2, Q, H)))
        A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
        D = jnp.ones((H,))
        h0 = jnp.zeros((2, H, dh, S))
        t = time_fn(lambda: ssd_chunk_pallas(x, B, C, dt, A, D, h0))
        vmem = Q * (dh + 2 * S) * 4 + Q * Q * 4 + dh * S * 4
        emit(f"kernel/ssd_chunk_Q{Q}_S{S}", t,
             f"vmem_per_headblk_B={vmem};fused decay+scores+state")


if __name__ == "__main__":
    # `--only decode-attn` is the nightly short-length smoke (CI runs it as
    # `python -m benchmarks.kernel_bench` from the repo root)
    import sys
    print("name,us_per_call,derived")
    if "--only" in sys.argv:
        which = sys.argv[sys.argv.index("--only") + 1]
        {"decode-attn": run_decode_attn, "verify-attn": run_verify_attn,
         "ssm": run_ssm}[which]()
    else:
        run()
