"""Oracle for int8-KV decode attention (the paper's dMVM, Fig. 13)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import quant

NEG_INF = -1e30


def ref(q, k_q, k_s, v_q, v_s, length, out_dtype=None):
    """q: [B,1,H,D] float; k_q/v_q: [B,S,G,D] int8; k_s/v_s: [B,S,G,1] f32."""
    B, _, H, D = q.shape
    G = k_q.shape[2]
    rep = H // G
    qh = q.reshape(B, H, D)
    q_q, q_s = quant.quantize_kv(qh)
    q_q = q_q.reshape(B, G, rep, D)
    q_s = q_s.reshape(B, G, rep, 1)
    s_int = jnp.einsum("bgrd,bsgd->bgrs", q_q.astype(jnp.int32),
                       k_q.astype(jnp.int32))
    k_sc = k_s[..., 0].transpose(0, 2, 1)[:, :, None, :]
    scores = s_int.astype(jnp.float32) * q_s * k_sc / math.sqrt(D)
    S = k_q.shape[1]
    mask = jnp.arange(S)[None, None, None, :] < length
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    vf = v_q.astype(jnp.float32) * v_s
    o = jnp.einsum("bgrs,bsgd->bgrd", w, vf)
    return o.reshape(B, 1, H, D).astype(out_dtype or q.dtype)


def verify_tree_ref(q, k_q, k_s, v_q, v_s, pos, anc, out_dtype=None):
    """Tree-verify oracle: q: [B,T,H,D] float (T tree nodes per slot at
    cache rows ``pos[b]..pos[b]+T-1``; node 0 is the root / last committed
    token).  Node t of slot b attends the committed prefix (keys
    ``< pos[b]``) plus in-window key ``pos[b]+j`` iff bit j of
    ``anc[b, t]`` (int32 ancestor-or-self bitmask) is set."""
    B, T, H, D = q.shape
    G = k_q.shape[2]
    rep = H // G
    q_q, q_s = quant.quantize_kv(q.reshape(B, T * H, D))
    q_q = q_q.reshape(B, T, G, rep, D)
    q_s = q_s.reshape(B, T, G, rep, 1)
    s_int = jnp.einsum("btgrd,bsgd->btgrs", q_q.astype(jnp.int32),
                       k_q.astype(jnp.int32))
    k_sc = k_s[..., 0].transpose(0, 2, 1)[:, None, :, None, :]   # [B,1,G,1,S]
    scores = s_int.astype(jnp.float32) * q_s * k_sc / math.sqrt(D)
    S = k_q.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    idx = jnp.arange(S, dtype=jnp.int32)[None, :] - pos[:, None]    # [B,S]
    committed = idx < 0
    in_win = (idx >= 0) & (idx < T)
    anc = jnp.asarray(anc, jnp.int32)
    bit = jax.lax.shift_right_logical(
        anc[:, :, None], jnp.clip(idx, 0, 31)[:, None, :]) & 1     # [B,T,S]
    mask = committed[:, None, :] | (in_win[:, None, :] & (bit == 1))
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    vf = v_q.astype(jnp.float32) * v_s
    o = jnp.einsum("btgrs,bsgd->btgrd", w, vf)
    return o.reshape(B, T, H, D).astype(out_dtype or q.dtype)
