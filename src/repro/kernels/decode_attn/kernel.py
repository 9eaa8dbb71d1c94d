"""Flash-decoding Pallas kernel over the int8 SLC KV cache (dMVM).

Grid: (batch, seq blocks).  Each step reads one ``[bs, G, D]`` block of
the ``[B, S, G, D]`` pool — every KV-head group at once, so the block's
last two dims are the array's own ``(G, D)`` and Mosaic's (8, 128) tiling
rule holds for any head count and head dim — and performs the paper's two
dMVM roles on it:

  * ``q . K^T`` — integer VVMs: int8 q x int8 K block -> int32, batched
    over the G groups, then descaled (the SLC page read + RPU stream
    multiply of Fig. 13b-c);
  * ``S . V``   — the row-wise product: per-position softmax weights (with
    V's per-row scale folded in) scale V rows and accumulate (Fig. 13e-f),
    so the growing sequence axis is streamed, never transposed.

The per-row K/V scales enter as ``[B, G, S]`` (blocks ``(G, bs)``), the
lane-major layout the ``[G, rows, bs]`` score tile broadcasts against.

Running (max, denom, acc) streaming-softmax state lives in VMEM scratch and
persists across the (sequential) seq-block grid dimension, finalising on the
last block — the same one-pass rescaling the H-tree RPUs pipeline.

Fully-masked key blocks are skipped: each batch row reads its per-row key
limits from SMEM and predicates the whole dMVM body on the block holding a
visible key, so a short-context slot in a long-``max_len`` pool stops paying
for ``cdiv(max_len, bs)`` blocks of NEG_INF work (every row sees key 0 — the
decode limits are ``pos + 1 >= 1`` and a tree root is its own ancestor — so
block 0 always computes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BLOCK_S = 256
NEG_INF = -1e30


def _dmvm_block(q_ref, qs_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                m_ref, l_ref, acc_ref, *, n_s: int, bs: int, d: int,
                live, visible):
    """Shared body: ``live`` (traced bool) says whether any row sees a key
    of this block; ``visible(kpos [1, bs]) -> [R, bs]`` is the row mask."""
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        k = jnp.swapaxes(k_ref[...], 0, 1)               # [G, bs, D] int8
        s_int = jax.lax.dot_general(                     # s8 x s8 -> s32
            q_ref[...], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)            # [G, R, bs]
        scores = (s_int.astype(jnp.float32) * qs_ref[...]
                  * ks_ref[...][:, None, :] * (1.0 / math.sqrt(d)))
        kpos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        vis = visible(kpos)[None]                        # [1, R, bs]
        scores = jnp.where(vis, scores, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        # masked keys weigh exactly zero, also over a ragged tail block
        pv = jnp.where(vis, p * vs_ref[...][:, None, :], 0.0)
        vf = jnp.swapaxes(v_ref[...], 0, 1).astype(jnp.float32)  # [G, bs, D]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pv, vf, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)          # row-wise product
        m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _final():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(len_ref, *refs, t: int, rep: int, bs: int, **kw):
    """The plain decode step (``t == 1``): batch row ``b`` masks keys to
    its cache length ``len_ref[b, 0]``."""
    lim = len_ref[pl.program_id(0), 0]
    _dmvm_block(*refs, bs=bs, live=pl.program_id(1) * bs < lim,
                visible=lambda kpos: kpos < jnp.full((rep, 1), lim), **kw)


def _tree_kernel(pos_ref, anc_ref, *refs, t: int, rep: int, bs: int, **kw):
    """Tree-verify variant: the ``t`` query tokens are the nodes of a draft
    *tree* whose rows land at cache positions ``pos .. pos + t - 1``.  Row
    ``r`` (node ``r // rep``) sees the committed prefix (keys
    ``< pos_ref[b, 0]``) plus exactly the in-window keys whose node index
    is an ancestor-or-self of its node — bit ``j`` of ``anc_ref[b, r //
    rep]`` (int32, so t <= 31 in-window bits stay in the sign-safe range).
    A chain, anc[i] = (1 << (i+1)) - 1, gives the stepped causal mask of
    t sequential decode steps."""
    b_idx = pl.program_id(0)
    base = pos_ref[b_idx, 0]
    ancs = [anc_ref[b_idx, i] for i in range(t)]      # t scalar SMEM reads

    def visible(kpos):
        idx = kpos - base                             # in-window node index
        anc = jnp.stack(ancs).reshape(t, 1)
        anc = jnp.broadcast_to(anc, (t, rep)).reshape(t * rep, 1)
        bit = jax.lax.shift_right_logical(anc, jnp.clip(idx, 0, 31)) & 1
        return (kpos < base) | ((idx >= 0) & (idx < t) & (bit == 1))

    # no row sees past the window's last node (base + t - 1); blocks past it
    # are fully masked — same dead-block skip as the decode kernel
    _dmvm_block(*refs, bs=bs, live=pl.program_id(1) * bs < base + t,
                visible=visible, **kw)


def _attn_pallas(kernel, smem, q_q, q_s, k_q, k_s, v_q, v_s, *, t: int,
                 rep: int, bs: int, interpret: bool | None):
    """Shared launch: ``smem`` are the per-row scalar operands; q_q/q_s
    rows are [B, G, t*rep, ...]; k_s/v_s are the pool's [B, S, G, 1]."""
    B, G, R, D = q_q.shape
    S = k_q.shape[1]
    bs = min(bs, S)
    n_s = pl.cdiv(S, bs)
    ks = k_s[..., 0].transpose(0, 2, 1)                   # [B, G, S]
    vs = v_s[..., 0].transpose(0, 2, 1)
    kv_spec = pl.BlockSpec((None, bs, G, D), lambda b, s: (b, s, 0, 0))
    sc_spec = pl.BlockSpec((None, G, bs), lambda b, s: (b, 0, s))
    row_spec = pl.BlockSpec((None, G, R, D), lambda b, s: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, n_s=n_s, bs=bs, d=D, t=t, rep=rep),
        grid=(B, n_s),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(smem) + [
            row_spec,
            pl.BlockSpec((None, G, R, 1), lambda b, s: (b, 0, 0, 0)),
            kv_spec, sc_spec, kv_spec, sc_spec,
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, R, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, R, 1), jnp.float32),
            pltpu.VMEM((G, R, 1), jnp.float32),
            pltpu.VMEM((G, R, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*smem, q_q, q_s, k_q, ks, v_q, vs)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attn_pallas(q_q, q_s, k_q, k_s, v_q, v_s, length, *,
                       bs: int = BLOCK_S, interpret: bool | None = None):
    """q_q: [B,G,rep,D] int8; q_s: [B,G,rep,1] f32; k_q/v_q: [B,S,G,D] int8;
    k_s/v_s: [B,S,G,1] f32; length: [B] (or [1], broadcast) int32 per-slot
    cache lengths -> out [B,G,rep,D] f32."""
    B, G, rep, D = q_q.shape
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1, 1),
                               (B, 1))
    return _attn_pallas(_decode_kernel, (lengths,), q_q, q_s, k_q, k_s, v_q,
                        v_s, t=1, rep=rep, bs=bs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def verify_tree_attn_pallas(q_q, q_s, k_q, k_s, v_q, v_s, pos, anc, *,
                            bs: int = BLOCK_S, interpret: bool | None = None):
    """Tree-verify flash decoding: q_q: [B,G,T,rep,D] int8 (T tree nodes
    per slot at cache rows ``pos .. pos + T - 1``; node 0 is the last
    committed token / tree root); ``pos``: [B] int32 committed-prefix
    cursors; ``anc``: [B,T] int32 per-node ancestor bitmasks (bit j set
    iff node j is an ancestor-or-self of node i) -> [B,G,T,rep,D] f32.
    T folds into the q row axis, so the dMVM dataflow is the T=1 kernel's
    with a per-row (committed prefix) | (ancestor bit) mask."""
    B, G, T, rep, D = q_q.shape
    smem = (jnp.asarray(pos, jnp.int32).reshape(B, 1),
            jnp.asarray(anc, jnp.int32))
    out = _attn_pallas(_tree_kernel, smem,
                       q_q.reshape(B, G, T * rep, D),
                       q_s.reshape(B, G, T * rep, 1),
                       k_q, k_s, v_q, v_s, t=T, rep=rep, bs=bs,
                       interpret=interpret)
    return out.reshape(B, G, T, rep, D)
