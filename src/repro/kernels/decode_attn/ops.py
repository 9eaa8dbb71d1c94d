"""jit'd wrapper: model-facing decode attention -> Pallas flash-decoding."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import quant
from repro.core.kvcache import slot_positions
from repro.kernels.decode_attn import kernel as K


def decode_attention(q, k_q, k_s, v_q, v_s, length,
                     interpret: bool | None = None):
    """q: [B,1,H,D] float; k_q/v_q: [B,S,G,D] int8; k_s/v_s: [B,S,G,1] f32;
    length: scalar int32 (aligned batch) or [B] per-slot lengths
    -> [B,1,H,D]."""
    B, _, H, D = q.shape
    G = k_q.shape[2]
    rep = H // G
    qh = q.reshape(B, H, D)
    q_q, q_s = quant.quantize_kv(qh)
    q_q = q_q.reshape(B, G, rep, D)
    q_s = q_s.reshape(B, G, rep, 1)
    ln = slot_positions(length, B)
    out = K.decode_attn_pallas(q_q, q_s, k_q, k_s, v_q, v_s, ln,
                               interpret=interpret)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def verify_attention_tree(q, k_q, k_s, v_q, v_s, pos, anc,
                          interpret: bool | None = None):
    """Tree-verify attention: q: [B,T,H,D] float (T draft-tree nodes per
    slot at rows ``pos[b]..pos[b]+T-1``; node 0 = root / last committed
    token); ``anc``: [B,T] int32 ancestor-or-self bitmasks.  Node t of
    slot b sees the committed prefix plus key ``pos[b]+j`` iff bit j of
    ``anc[b, t]`` is set -> [B,T,H,D]."""
    B, T, H, D = q.shape
    G = k_q.shape[2]
    rep = H // G
    q_q, q_s = quant.quantize_kv(q.reshape(B, T * H, D))
    q_q = q_q.reshape(B, T, G, rep, D).transpose(0, 2, 1, 3, 4)
    q_s = q_s.reshape(B, T, G, rep, 1).transpose(0, 2, 1, 3, 4)
    pos_b = slot_positions(pos, B)
    out = K.verify_tree_attn_pallas(q_q, q_s, k_q, k_s, v_q, v_s, pos_b,
                                    jnp.asarray(anc, jnp.int32),
                                    interpret=interpret)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, T, H, D).astype(q.dtype)
