"""Attention: GQA / MHA / MLA; chunked (flash-style) training attention and
int8-KV decode attention (the paper's dMVM, Sec. IV-B / Fig. 13).

Decode attention computes ``q . K^T`` and ``S . V`` directly against the int8
"SLC-region" cache: scores accumulate in int8 x int8 -> int32 and are
descaled, exactly the flash-PIM dataflow (q broadcast over K rows = VVMs;
S scattered over V rows = VSMs / row-wise product).  The sequence dimension
is never transposed or gathered — for seq-sharded caches (long_500k) the
partial-softmax statistics combine across shards via LSE (psum under GSPMD).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.core import kvcache as KV
from repro.models import layers as L

Params = dict[str, Any]
NEG_INF = -1e30


def _scoped(name: str):
    """Run the wrapped layer under ``jax.named_scope(name)``, so that a
    profile attributes its device time to ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 8)
    if cfg.attn_type == "mla":
        qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        p = {
            "wq_a": L.dense_init(ks[0], d, cfg.q_lora_rank, dtype)["w"],
            "q_norm": L.norm_init(cfg.q_lora_rank),
            "wq_b": L.dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_head, dtype)["w"],
            "wkv_a": L.dense_init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype)["w"],
            "kv_norm": L.norm_init(cfg.kv_lora_rank),
            "wkv_b": L.dense_init(ks[3], cfg.kv_lora_rank,
                                  cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype)["w"],
            "wo": L.dense_init(ks[4], cfg.n_heads * cfg.v_head_dim, d, dtype)["w"],
        }
        return p
    p = {
        "wq": L.dense_init(ks[0], d, cfg.n_heads * hd, dtype)["w"],
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype)["w"],
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype)["w"],
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, d, dtype)["w"],
    }
    if cfg.use_qk_norm:
        p["q_norm"] = L.norm_init(hd)
        p["k_norm"] = L.norm_init(hd)
    return p


# ---------------------------------------------------------------------------
# full (training / prefill) attention — chunked over KV to bound memory
# ---------------------------------------------------------------------------
def _causal_chunk_mask(q_pos, k_pos):
    return (k_pos[None, :] <= q_pos[:, None])


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    kv_block: int = 1024, kv_lengths=None,
                    scale: float | None = None) -> jax.Array:
    """Memory-bounded attention: lax.scan over KV blocks with running
    (max, denom) statistics.  q: [B, Tq, H, Dk]; k: [B, Tk, G, Dk];
    v: [B, Tk, G, Dv] with G = kv heads (GQA groups computed natively —
    no head replication is ever materialised).  FLOPs match dense attention.

    ``kv_lengths`` ([B] int32, optional) masks keys at and beyond each
    request's true prompt length — ragged right-padded batches attend only
    to their own valid prefix.  ``scale`` replaces the softmax scale
    ``1/sqrt(Dk)`` (MLA's YaRN temperature).
    """
    B, Tq, H, Dk = q.shape
    G = k.shape[2]
    Dv = v.shape[-1]
    rep = H // G
    Tk = k.shape[1]
    blk = min(kv_block, Tk)
    n_blocks = math.ceil(Tk / blk)
    pad = n_blocks * blk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blocks, blk, G, Dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, blk, G, Dv).transpose(1, 0, 2, 3, 4)
    q5 = (q.astype(jnp.float32) / math.sqrt(Dk) if scale is None
          else q.astype(jnp.float32) * scale).reshape(B, Tq, G, rep, Dk)
    q_pos = jnp.arange(Tq) + q_offset

    def step(carry, xs):
        m, l, acc = carry
        kblk, vblk, bidx = xs
        k_pos = bidx * blk + jnp.arange(blk)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q5, kblk.astype(jnp.float32))
        mask = (_causal_chunk_mask(q_pos, k_pos) if causal
                else jnp.ones((Tq, blk), bool))
        valid = (k_pos < Tk)
        mask = (mask & valid[None, :])[None]                 # [1, Tq, blk]
        if kv_lengths is not None:
            # ragged batch: key b is live only below its request's length
            mask = mask & (k_pos[None, None, :] < kv_lengths[:, None, None])
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, G, rep, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, G, rep, Tq), jnp.float32)
    a0 = jnp.zeros((B, G, rep, Tq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # [B,G,rep,Tq,Dv]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Dv).astype(q.dtype)


def gqa_forward(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                backend: str = "dense", lengths: jax.Array | None = None,
                rt=None) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Training / prefill GQA.  Returns (out, (k, v)) for KV caching.
    ``lengths`` ([B], optional) masks padding keys in ragged batches.
    Passing ``rt`` (a Runtime with a mesh) routes RoPE through the
    partition-safe contraction form, like the chunked path — the serve
    engine's atomic prefill does; the training path stays on the
    single-device rotate-half form."""
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = L.apply_linear(L._lin(p, "wq"), x, backend).reshape(B, T, cfg.n_heads, hd)
    k = L.apply_linear(L._lin(p, "wk"), x, backend).reshape(B, T, cfg.n_kv_heads, hd)
    v = L.apply_linear(L._lin(p, "wv"), x, backend).reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.use_qk_norm:
        q = L.apply_norm(p["q_norm"], q, cfg.norm_eps)
        k = L.apply_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta:
        q = _rope(q, positions, cfg.rope_theta, rt)
        k = _rope(k, positions, cfg.rope_theta, rt)
    o = flash_attention(q, k, v, kv_lengths=lengths)
    out = L.apply_linear(L._lin(p, "wo"), o.reshape(B, T, -1), backend)
    return out, (k, v)


# ---------------------------------------------------------------------------
# chunked prefill: consume [B, C] tokens at an arbitrary cursor
# ---------------------------------------------------------------------------
def _rope(t: jax.Array, positions: jax.Array, theta: float, rt,
          freqs=None) -> jax.Array:
    """RoPE for the prefill paths (atomic and chunked): the partition-safe
    contraction form under a mesh (rotate-half's split+concat
    mis-partitions deferred partial sums, triggering SPMD full-
    rematerialization copies — see :func:`layers.apply_rope_spmd`), the
    bit-exact elementwise form on a single device."""
    if rt is not None and rt.mesh is not None:
        return L.apply_rope_spmd(t, positions, theta, freqs)
    return L.apply_rope(t, positions, theta, freqs)


def gqa_chunk(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
              buf: dict, start: jax.Array, kv_lengths: jax.Array,
              rt=None) -> tuple[jax.Array, dict]:
    """One chunk of a chunked prefill.  ``x``: [B, C, d] hidden chunk whose
    tokens sit at ``positions`` (= start + arange(C)); ``buf`` carries the
    float K/V of the whole in-flight prompt ([B, S_buf, H_kv, D]).

    The chunk's k/v append at offset ``start`` (:func:`KV.chunk_update`) and
    q attends over the full resident prefix [0, kv_lengths) — full-precision
    like one-shot prefill, so chunked == unchunked token-for-token.
    ``start`` is traced: one compile serves every cursor.  Returns
    (out, updated buf)."""
    backend = rt.backend if rt is not None else "dense"
    B, C, _ = x.shape
    hd = cfg.head_dim
    q = L.apply_linear(L._lin(p, "wq"), x, backend).reshape(B, C, cfg.n_heads, hd)
    k = L.apply_linear(L._lin(p, "wk"), x, backend).reshape(B, C, cfg.n_kv_heads, hd)
    v = L.apply_linear(L._lin(p, "wv"), x, backend).reshape(B, C, cfg.n_kv_heads, hd)
    if cfg.use_qk_norm:
        q = L.apply_norm(p["q_norm"], q, cfg.norm_eps)
        k = L.apply_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta:
        q = _rope(q, positions, cfg.rope_theta, rt)
        k = _rope(k, positions, cfg.rope_theta, rt)
    k_buf = KV.chunk_update(buf["k"], k, start)
    v_buf = KV.chunk_update(buf["v"], v, start)
    o = flash_attention(q, k_buf.astype(q.dtype), v_buf.astype(q.dtype),
                        q_offset=start, kv_lengths=kv_lengths)
    out = L.apply_linear(L._lin(p, "wo"), o.reshape(B, C, -1), backend)
    return out, {"k": k_buf, "v": v_buf}


@_scoped("mla")
def mla_chunk(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
              buf: dict, start: jax.Array, kv_lengths: jax.Array,
              rt=None) -> tuple[jax.Array, dict]:
    """Chunked-prefill MLA: like :func:`mla_forward` but against carried
    float K/V buffers; the compressed latent of the chunk is appended to
    ``buf["lat"]`` so finalization can quantize it into the SLC cache."""
    backend = rt.backend if rt is not None else "dense"
    B, C, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    freqs = mla_rope_freqs(cfg)
    q_lat = L.apply_norm(p["q_norm"], L.apply_linear(L._lin(p, "wq_a"), x, backend),
                         cfg.norm_eps)
    q = L.apply_linear(L._lin(p, "wq_b"), q_lat, backend).reshape(B, C, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _rope(q_rope, positions, cfg.rope_theta, rt, freqs)

    kv_a = L.apply_linear(L._lin(p, "wkv_a"), x, backend)
    c_kv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    c_kv = L.apply_norm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = _rope(k_rope[:, :, None, :], positions, cfg.rope_theta, rt,
                   freqs)
    kv = L.apply_linear(L._lin(p, "wkv_b"), c_kv, backend).reshape(B, C, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, C, H, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)

    k_buf = KV.chunk_update(buf["k"], k, start)
    v_buf = KV.chunk_update(buf["v"], v, start)
    # the latent's two halves are carried separately and concatenated at
    # finalize time: concatenating them here hits the same SPMD
    # partial-sum mispartition as rotate-half (see _rope)
    lat_c = KV.chunk_update(buf["lat_c"], c_kv, start)
    lat_r = KV.chunk_update(buf["lat_r"], k_rope[:, :, 0, :], start)
    o = flash_attention(qf, k_buf.astype(qf.dtype), v_buf.astype(qf.dtype),
                        q_offset=start, kv_lengths=kv_lengths,
                        scale=mla_softmax_scale(cfg))
    out = L.apply_linear(L._lin(p, "wo"), o.reshape(B, C, -1), backend)
    return out, {"k": k_buf, "v": v_buf, "lat_c": lat_c, "lat_r": lat_r}


# ---------------------------------------------------------------------------
# decode attention against the int8 SLC cache (dMVM)
# ---------------------------------------------------------------------------
def _pv_rows(w: jax.Array, v_q: jax.Array, inter_dtype) -> jax.Array:
    """``w`` [B, T, H, S] f32 weights against ``v_q`` [B, S, C] int8 rows
    -> [B, T, H, C] f32, every head against every lane.

    With an f32 ``inter_dtype`` the weights split into three bf16 parts
    whose sum is ``w`` exactly (8 + 8 + 8 significand bits), and an int8
    value is exact in bf16, so every product is exact and accumulates in
    f32: the arithmetic of an f32 dot at HIGHEST precision, as one bf16
    MXU dot whose row axis carries the three parts (the rows of V are
    read once) and whose sum over them fuses into its output.  A lower
    ``inter_dtype`` (bf16 dMVM) is one dot in that type."""
    if jnp.dtype(inter_dtype) != jnp.float32:
        return jnp.einsum("bths,bsc->bthc", w.astype(inter_dtype),
                          v_q.astype(inter_dtype),
                          preferred_element_type=jnp.float32)
    hi = w.astype(jnp.bfloat16)
    rest = w - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    parts = jnp.stack([hi, mid, lo], axis=2)               # [B,T,3,H,S]
    return jnp.einsum("btkhs,bsc->btkhc", parts, v_q.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).sum(axis=2)


def _lane_dense_attend(q: jax.Array, k_q, k_s, v_q, v_s, mask: jax.Array,
                       inter_dtype) -> jax.Array:
    """int8 dMVM of ``q`` ([B, T, H, D] float) over a lane-dense cache:
    ``k_q``/``v_q`` [B, S, Hkv·D] int8 (heads and head_dim merged in the
    minor axis), ``k_s``/``v_s`` [B, S, Hkv, 1] f32; ``mask`` broadcasts
    to [B, T, H, S].

    Each head's int8 q sits in its KV group's D lanes of an otherwise zero
    [Hkv·D] row, so QK^T is one int8 dot of those rows against the cache
    rows as they lie (int32 accumulate, exact).  PV dots the softmax
    weights, V scales folded in per head, against the cache rows
    (:func:`_pv_rows`), then keeps each head's own D lanes; the barrier
    stops XLA from fusing that pick back into the dot as a grouped
    convolution, which would copy the V slab.  Nothing reads the cache in
    any other layout.  Both dots do Hkv times the multiply-adds a
    per-head layout needs, over the same bytes of K/V."""
    B, T, H, D = q.shape
    G = k_s.shape[2]
    own = (jnp.arange(H)[:, None] // (H // G)
           == jnp.arange(G)[None, :])[..., None]            # [H, G, 1]
    q_q, q_scale = quant.quantize_kv(q)                    # per-(B,T,H)
    q_lanes = jnp.where(own, q_q[:, :, :, None, :],
                        jnp.int8(0)).reshape(B, T, H, G * D)
    s_int = jnp.einsum("bthc,bsc->bths", q_lanes, k_q,
                       preferred_element_type=jnp.int32)
    per_head = lambda s: jnp.repeat(s[..., 0].transpose(0, 2, 1), H // G,
                                    axis=1)[:, None]       # [B,1,H,S]
    scores = (s_int.astype(jnp.float32) * q_scale * per_head(k_s)
              / math.sqrt(D))
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)                    # controller op, fp32
    o_all = jax.lax.optimization_barrier(
        _pv_rows(w * per_head(v_s), v_q, inter_dtype))     # [B,T,H,Hkv·D]
    return jnp.where(own, o_all.reshape(B, T, H, G, D), 0.0).sum(axis=3)


def decode_attention_int8(q: jax.Array, k_q, k_s, v_q, v_s, length: jax.Array,
                          backend: str = "dense",
                          inter_dtype=jnp.float32) -> jax.Array:
    """q: [B, 1, H, D] float; cache: [B, S, Hkv·D] int8 lane-dense rows
    (:mod:`repro.core.kvcache`) with [B, S, Hkv, 1] f32 scales.

    QK^T as integer VVMs (q quantized per-head), SV as the row-wise product:
    softmax weights scatter over V rows, never transposing the S axis
    (:func:`_lane_dense_attend`).  GQA groups are computed natively (no
    cache replication).  ``length`` is a scalar (aligned batch) or a [B]
    vector of per-slot cache lengths (continuous batching: every slot masks
    to its own resident prefix).
    """
    B = q.shape[0]
    if backend == "fused_int8":
        from repro.kernels.decode_attn import ops as da_ops
        G = k_s.shape[2]
        return da_ops.decode_attention(q, KV.split_heads(k_q, G), k_s,
                                       KV.split_heads(v_q, G), v_s, length)
    lengths = KV.slot_positions(length, B)
    S = k_q.shape[1]
    mask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    o = _lane_dense_attend(q, k_q, k_s, v_q, v_s, mask, inter_dtype)
    return o.astype(q.dtype)


def tree_visibility_mask(pos_b: jax.Array, anc: jax.Array, S: int,
                         T: int) -> jax.Array:
    """[B, T, S] bool tree-verify visibility: node ``t`` of slot ``b`` sees
    the committed prefix (keys ``< pos_b[b]``) plus in-window key
    ``pos_b[b]+j`` iff bit j of ``anc[b, t]`` (int32 ancestor-or-self
    bitmask; node 0 = root = last committed token) is set.  A chain's
    mask, ``anc[i] = (1 << (i+1)) - 1``, is the stepped causal mask of T
    sequential decode steps."""
    idx = jnp.arange(S, dtype=jnp.int32)[None, :] - pos_b[:, None]   # [B,S]
    committed = idx < 0
    in_win = (idx >= 0) & (idx < T)
    bit = jax.lax.shift_right_logical(
        jnp.asarray(anc, jnp.int32)[:, :, None],
        jnp.clip(idx, 0, 31)[:, None, :]) & 1                        # [B,T,S]
    return committed[:, None, :] | (in_win[:, None, :] & (bit == 1))


def verify_attention_int8(q: jax.Array, k_q, k_s, v_q, v_s, pos: jax.Array,
                          anc: jax.Array, backend: str = "dense",
                          inter_dtype=jnp.float32) -> jax.Array:
    """Speculative-verify attention: ``q`` is [B, T, H, D] — the T nodes of
    a per-slot draft *tree* whose K/V rows sit at ``pos[b] .. pos[b]+T-1``
    (node 0 = root = the last committed token); cache layout as in
    :func:`decode_attention_int8`.  ``anc`` ([B, T] int32) holds each
    node's ancestor-or-self bitmask, and the mask is
    :func:`tree_visibility_mask`: node ``t`` sees the committed prefix and
    its own root-path.  The T query rows run through the same dMVM einsums
    as the T=1 decode (:func:`_lane_dense_attend`): int8xint8 scores are
    exact integer arithmetic.

    A node's unmasked keys hold exactly the values sequential decode of its
    root-path would see.  Nodes whose ancestor set is a window *prefix*
    (chain-prefix nodes; every node of a chain, whose mask is the stepped
    causal one) stay bit-exact with sequential decode, so acceptance
    decisions match step-by-step decode bit-for-bit; a node whose path
    skips an interleaved sibling sees the same visible values at shifted
    lane positions — masked keys weigh exactly zero, but the vectorised
    softmax/PV reductions associate across lanes differently, so those
    rows match only up to float reduction order (~1 ulp; the engine's
    greedy token parity is pinned by test seeds, like the warm prefix bar
    in DESIGN.md Sec. 1g).
    """
    B, T, H, D = q.shape
    pos_b = KV.slot_positions(pos, B)
    if backend == "fused_int8":
        from repro.kernels.decode_attn import ops as da_ops
        G = k_s.shape[2]
        return da_ops.verify_attention_tree(
            q, KV.split_heads(k_q, G), k_s, KV.split_heads(v_q, G), v_s,
            pos_b, anc)
    mask = tree_visibility_mask(pos_b, anc, k_q.shape[1], T)[:, :, None, :]
    o = _lane_dense_attend(q, k_q, k_s, v_q, v_s, mask, inter_dtype)
    return o.astype(q.dtype)


def gqa_decode_rows(p: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, backend: str = "dense"):
    """The projections of decode tokens: ``x`` [B, T, d] at ``positions``
    ([B, T]; one token at ``pos_b[:, None]``, a verify window at the
    cursor plus its offsets or tree depths) -> (q [B, T, H, D],
    (k_q, k_s, v_q, v_s)), the tokens' int8 K/V rows in the pool's
    lane-dense layout ([B, T, Hkv·D]) and their f32 scales
    ([B, T, Hkv, 1]) for the caller to append."""
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = L.apply_linear(L._lin(p, "wq"), x, backend).reshape(B, T, cfg.n_heads, hd)
    k = L.apply_linear(L._lin(p, "wk"), x, backend).reshape(B, T, cfg.n_kv_heads, hd)
    v = L.apply_linear(L._lin(p, "wv"), x, backend).reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.use_qk_norm:
        q = L.apply_norm(p["q_norm"], q, cfg.norm_eps)
        k = L.apply_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    (k_q, k_s), (v_q, v_s) = KV.quantize_rows(k), KV.quantize_rows(v)
    return q, (k_q, k_s, v_q, v_s)


def gqa_decode_out(p: Params, o: jax.Array, backend: str = "dense"):
    """The output projection of decode tokens' attention ``o``
    ([B, T, H, D]) -> [B, T, d]."""
    return L.apply_linear(L._lin(p, "wo"), o.reshape(o.shape[:2] + (-1,)),
                          backend)


def gqa_decode(p: Params, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
               k_q, k_s, v_q, v_s, backend: str = "dense",
               inter_dtype=jnp.float32):
    """One-token decode against one layer's cache ([B, S, Hkv·D] rows,
    [B, S, Hkv, 1] scales).
    Returns (out, updated (k_q, k_s, v_q, v_s)).  ``pos`` is a scalar
    (aligned batch) or [B] vector of per-slot positions — each slot's k/v
    appends at its own SLC offset (vmapped update), and the token attends
    over the cache including its own row.  The decoder-only layer scan
    appends into the stacked pool instead
    (:func:`repro.models.transformer.apply_layer_decode`)."""
    pos_b = KV.slot_positions(pos, x.shape[0])
    q, rows = gqa_decode_rows(p, cfg, x, pos_b[:, None], backend)
    k_q, k_s, v_q, v_s = (KV.batched_update(c, r, pos_b)
                          for c, r in zip((k_q, k_s, v_q, v_s), rows))
    o = decode_attention_int8(q, k_q, k_s, v_q, v_s, pos_b + 1, backend,
                              inter_dtype)
    return gqa_decode_out(p, o, backend), (k_q, k_s, v_q, v_s)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): compressed-latent cache; absorbed decode
# ---------------------------------------------------------------------------
def mla_rope_freqs(cfg: ModelConfig):
    """Inverse frequencies of MLA's rope dims: YaRN's where the config
    stretches the context, else None (plain ``rope_theta``)."""
    if not cfg.yarn_factor:
        return None
    return L.yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                        cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                        cfg.yarn_beta_slow)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``1/sqrt(qk_nope + qk_rope)``, times YaRN's ``mscale^2`` where the
    config stretches the context and sets ``mscale_all_dim``."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _quantize_latent(latent: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-token symmetric int8 for the MLA latent rows ([..., r+dr]).
    Shared by decode and verify so their SLC rows stay bit-identical —
    the speculative lane's acceptance test depends on it."""
    amax = jnp.max(jnp.abs(latent.astype(jnp.float32)), axis=-1, keepdims=True)
    sc = jnp.maximum(amax, 1e-8) / 127.0
    lq = jnp.clip(jnp.round(latent / sc.astype(latent.dtype)),
                  -127, 127).astype(jnp.int8)
    return lq, sc


@_scoped("mla")
def mla_forward(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                backend: str = "dense", lengths: jax.Array | None = None,
                rt=None):
    """Training/prefill MLA.  Returns (out, latent) where latent =
    [B, T, kv_lora + rope] is what the SLC region caches.  ``rt`` routes
    RoPE partition-safe under a mesh (see :func:`gqa_forward`)."""
    B, T, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    freqs = mla_rope_freqs(cfg)
    q_lat = L.apply_norm(p["q_norm"], L.apply_linear(L._lin(p, "wq_a"), x, backend),
                         cfg.norm_eps)
    q = L.apply_linear(L._lin(p, "wq_b"), q_lat, backend).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _rope(q_rope, positions, cfg.rope_theta, rt, freqs)

    kv_a = L.apply_linear(L._lin(p, "wkv_a"), x, backend)
    c_kv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    c_kv = L.apply_norm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = _rope(k_rope[:, :, None, :], positions, cfg.rope_theta, rt,
                   freqs)  # [B,T,1,dr]
    kv = L.apply_linear(L._lin(p, "wkv_b"), c_kv, backend).reshape(B, T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = flash_attention(qf, k, v, kv_lengths=lengths,
                        scale=mla_softmax_scale(cfg))
    out = L.apply_linear(L._lin(p, "wo"), o.reshape(B, T, -1), backend)
    latent = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
    return out, latent


@_scoped("mla")
def mla_decode(p: Params, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
               c_q: jax.Array, c_s: jax.Array, backend: str = "dense",
               inter_dtype=jnp.float32):
    """Absorbed MLA decode: attention runs directly in the latent space, so
    the per-step dMVM touches only [S, kv_lora+rope] int8 — the paper's
    SLC-cache read, 14x smaller than per-head K/V."""
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    freqs = mla_rope_freqs(cfg)
    r = cfg.kv_lora_rank
    pos_b = KV.slot_positions(pos, B)
    q_lat = L.apply_norm(p["q_norm"], L.apply_linear(L._lin(p, "wq_a"), x, backend),
                         cfg.norm_eps)
    q = L.apply_linear(L._lin(p, "wq_b"), q_lat, backend).reshape(B, 1, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    pp = pos_b[:, None]
    q_rope = L.apply_rope(q_rope, pp, cfg.rope_theta, freqs)

    kv_a = L.apply_linear(L._lin(p, "wkv_a"), x, backend)
    c_new = L.apply_norm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    k_rope_new = L.apply_rope(kv_a[:, :, None, r:], pp, cfg.rope_theta,
                              freqs)[:, :, 0, :]
    latent_new = jnp.concatenate([c_new, k_rope_new], axis=-1)      # [B,1,r+dr]
    lq, sc = _quantize_latent(latent_new)
    c_q = KV.batched_update(c_q, lq, pos_b)
    c_s = KV.batched_update(c_s, sc, pos_b)

    wkv_b = (p["wkv_b"] if "wkv_b" in p else
             (p["wkv_b_q"].astype(jnp.float32) * p["wkv_b_s"])).reshape(r, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]                   # [r,H,dn],[r,H,dv]
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(inter_dtype),
                       w_uk.astype(inter_dtype))                    # absorb W_UK
    cache = c_q.astype(inter_dtype) * c_s.astype(inter_dtype)       # [B,S,r+dr]
    scores = (jnp.einsum("bhr,bsr->bhs", q_eff, cache[..., :r],
                         preferred_element_type=jnp.float32) +
              jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(inter_dtype),
                         cache[..., r:], preferred_element_type=jnp.float32))
    scores = scores * mla_softmax_scale(cfg)
    S = c_q.shape[1]
    mask = jnp.arange(S)[None, None, :] < (pos_b + 1)[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w.astype(inter_dtype), cache[..., :r],
                       preferred_element_type=jnp.float32)          # latent-space SV
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32)) # expand W_UV
    out = L.apply_linear(L._lin(p, "wo"), o.reshape(B, 1, -1).astype(x.dtype),
                         backend)
    return out, (c_q, c_s)


@_scoped("mla")
def mla_verify(p: Params, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
               c_q: jax.Array, c_s: jax.Array, depth: jax.Array,
               anc: jax.Array, backend: str = "dense",
               inter_dtype=jnp.float32):
    """Absorbed MLA decode over the T nodes of a per-slot draft tree — the
    speculative verify sibling of :func:`mla_decode`.  The T compressed
    latents append at the per-slot cursor (multi-token
    :func:`KV.batched_update`), RoPE'd at their tree depth ``depth``;
    node ``t`` masks the latent cache to the committed prefix plus its
    root-path (``anc``, :func:`tree_visibility_mask`), so every node scores
    against exactly the prefix sequential decode of its path would see —
    see :func:`repro.models.transformer.apply_layer_verify`."""
    B, T, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    freqs = mla_rope_freqs(cfg)
    r = cfg.kv_lora_rank
    pos_b = KV.slot_positions(pos, B)
    pp = pos_b[:, None] + depth
    q_lat = L.apply_norm(p["q_norm"], L.apply_linear(L._lin(p, "wq_a"), x, backend),
                         cfg.norm_eps)
    q = L.apply_linear(L._lin(p, "wq_b"), q_lat, backend).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, pp, cfg.rope_theta, freqs)

    kv_a = L.apply_linear(L._lin(p, "wkv_a"), x, backend)
    c_new = L.apply_norm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    k_rope_new = L.apply_rope(kv_a[:, :, None, r:], pp, cfg.rope_theta,
                              freqs)[:, :, 0, :]
    latent_new = jnp.concatenate([c_new, k_rope_new], axis=-1)      # [B,T,r+dr]
    lq, sc = _quantize_latent(latent_new)
    c_q = KV.batched_update(c_q, lq, pos_b)
    c_s = KV.batched_update(c_s, sc, pos_b)

    wkv_b = (p["wkv_b"] if "wkv_b" in p else
             (p["wkv_b_q"].astype(jnp.float32) * p["wkv_b_s"])).reshape(r, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
    q_eff = jnp.einsum("bthd,rhd->bthr", q_nope.astype(inter_dtype),
                       w_uk.astype(inter_dtype))
    cache = c_q.astype(inter_dtype) * c_s.astype(inter_dtype)       # [B,S,r+dr]
    scores = (jnp.einsum("bthr,bsr->bths", q_eff, cache[..., :r],
                         preferred_element_type=jnp.float32) +
              jnp.einsum("bthd,bsd->bths", q_rope.astype(inter_dtype),
                         cache[..., r:], preferred_element_type=jnp.float32))
    scores = scores * mla_softmax_scale(cfg)
    mask = tree_visibility_mask(pos_b, anc, c_q.shape[1], T)[:, :, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bths,bsr->bthr", w.astype(inter_dtype), cache[..., :r],
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bthr,rhd->bthd", o_lat, w_uv.astype(jnp.float32))
    out = L.apply_linear(L._lin(p, "wo"), o.reshape(B, T, -1).astype(x.dtype),
                         backend)
    return out, (c_q, c_s)
