"""Whisper-style encoder-decoder (audio frontend stubbed).

The encoder consumes precomputed frame embeddings (the conv frontend is a
stub per the assignment); the decoder is a standard causal LM with
cross-attention.  At serve time the cross-attention K/V are computed once
from the encoder output and live — quantized int8 — in the "SLC region"
alongside the self-attention cache (they are *static* per request, the most
QLC-like of all cache tensors)."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kvcache as KV
from repro.models import attention as A
from repro.models import layers as L
from repro.models.transformer import Runtime, tree_stack, _sinusoid_at

Params = dict[str, Any]


def _xattn_init(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, cfg.n_heads * hd, dtype)["w"],
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype)["w"],
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype)["w"],
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, d, dtype)["w"],
    }


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 6)
    enc_layers = [
        {"ln1": L.norm_init(cfg.d_model, cfg.norm_type),
         "attn": A.attn_init(k1, cfg, dtype),
         "ln2": L.norm_init(cfg.d_model, cfg.norm_type),
         "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)}
        for k1, k2 in zip(jax.random.split(ks[0], cfg.encoder_layers),
                          jax.random.split(ks[1], cfg.encoder_layers))
    ]
    dec_layers = [
        {"ln1": L.norm_init(cfg.d_model, cfg.norm_type),
         "attn": A.attn_init(k1, cfg, dtype),
         "ln_x": L.norm_init(cfg.d_model, cfg.norm_type),
         "xattn": _xattn_init(k2, cfg, dtype),
         "ln2": L.norm_init(cfg.d_model, cfg.norm_type),
         "mlp": L.mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)}
        for k1, k2, k3 in zip(jax.random.split(ks[2], cfg.n_layers),
                              jax.random.split(ks[3], cfg.n_layers),
                              jax.random.split(ks[4], cfg.n_layers))
    ]
    return {
        "embed": L.embed_init(ks[5], cfg.vocab_size, cfg.d_model, dtype),
        "enc": tree_stack(enc_layers),
        "dec": tree_stack(dec_layers),
        "ln_enc": L.norm_init(cfg.d_model, cfg.norm_type),
        "ln_f": L.norm_init(cfg.d_model, cfg.norm_type),
    }


def encode(p: Params, cfg: ModelConfig, frames: jax.Array, rt: Runtime) -> jax.Array:
    """frames: [B, S_enc, d] stubbed frontend output -> [B, S_enc, d]."""
    B, S, _ = frames.shape
    x = frames + L.sinusoidal_positions(S, cfg.d_model).astype(frames.dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(xx, pl):
        h = L.apply_norm(pl["ln1"], xx)
        hd = cfg.head_dim
        q = L.apply_linear(L._lin(pl["attn"], "wq"), h, rt.backend).reshape(B, S, cfg.n_heads, hd)
        k = L.apply_linear(L._lin(pl["attn"], "wk"), h, rt.backend).reshape(B, S, cfg.n_kv_heads, hd)
        v = L.apply_linear(L._lin(pl["attn"], "wv"), h, rt.backend).reshape(B, S, cfg.n_kv_heads, hd)
        o = A.flash_attention(q, k, v, causal=False)
        xx = xx + L.apply_linear(L._lin(pl["attn"], "wo"), o.reshape(B, S, -1), rt.backend)
        xx = xx + L.apply_mlp(pl["mlp"], L.apply_norm(pl["ln2"], xx), cfg.mlp_type, rt.backend)
        return xx, None

    body = jax.checkpoint(body) if rt.remat else body
    x, _ = jax.lax.scan(body, x, p["enc"])
    return L.apply_norm(p["ln_enc"], x)


def _cross_attn(pl, cfg, h, enc_kv, rt, decode=False):
    B, T = h.shape[:2]
    hd = cfg.head_dim
    q = L.apply_linear(L._lin(pl, "wq"), h, rt.backend).reshape(B, T, cfg.n_heads, hd)
    if decode:
        k_q, k_s, v_q, v_s = enc_kv
        o = A.decode_attention_int8(q, k_q, k_s, v_q, v_s,
                                    jnp.array(k_q.shape[1], jnp.int32))
    else:
        k, v = enc_kv
        o = A.flash_attention(q, k, v, causal=False)
    return L.apply_linear(L._lin(pl, "wo"), o.reshape(B, T, -1), rt.backend)


def forward_train(p: Params, cfg: ModelConfig, frames: jax.Array,
                  tokens: jax.Array, rt: Runtime) -> jax.Array:
    """Teacher-forced decoder over ``tokens`` attending to encoded frames."""
    enc = encode(p, cfg, frames, rt)
    B, T = tokens.shape
    x = p["embed"]["w"][tokens]
    x = x + L.sinusoidal_positions(T, cfg.d_model).astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    hd = cfg.head_dim

    def body(xx, pl):
        h = L.apply_norm(pl["ln1"], xx)
        mix, _ = A.gqa_forward(pl["attn"], cfg, h, positions, rt.backend)
        xx = xx + mix
        hx = L.apply_norm(pl["ln_x"], xx)
        k = L.apply_linear(L._lin(pl["xattn"], "wk"), enc, rt.backend).reshape(
            B, -1, cfg.n_kv_heads, hd)
        v = L.apply_linear(L._lin(pl["xattn"], "wv"), enc, rt.backend).reshape(
            B, -1, cfg.n_kv_heads, hd)
        xx = xx + _cross_attn(pl["xattn"], cfg, hx, (k, v), rt)
        xx = xx + L.apply_mlp(pl["mlp"], L.apply_norm(pl["ln2"], xx),
                              cfg.mlp_type, rt.backend)
        return xx, None

    body = jax.checkpoint(body) if rt.remat else body
    x, _ = jax.lax.scan(body, x, p["dec"])
    x = L.apply_norm(p["ln_f"], x)
    return jnp.einsum("btd,vd->btv", x, p["embed"]["w"].astype(x.dtype))


def lm_loss(p: Params, cfg: ModelConfig, frames, tokens, labels, rt: Runtime):
    logits = forward_train(p, cfg, frames, tokens, rt).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# serving: prefill (encoder + prompt) and cached decode
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    Ld = cfg.n_layers
    # lane-dense int8 rows, per-head scales (see repro.core.kvcache)
    kv = (Ld, batch, max_len, cfg.n_kv_heads * cfg.head_dim)
    sc = (Ld, batch, max_len, cfg.n_kv_heads, 1)
    xe = (Ld, batch, cfg.encoder_seq, cfg.n_kv_heads * cfg.head_dim)
    xs = (Ld, batch, cfg.encoder_seq, cfg.n_kv_heads, 1)
    return {
        "k_q": jnp.zeros(kv, jnp.int8), "k_s": jnp.zeros(sc, jnp.float32),
        "v_q": jnp.zeros(kv, jnp.int8), "v_s": jnp.zeros(sc, jnp.float32),
        "xk_q": jnp.zeros(xe, jnp.int8), "xk_s": jnp.zeros(xs, jnp.float32),
        "xv_q": jnp.zeros(xe, jnp.int8), "xv_s": jnp.zeros(xs, jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(p: Params, cfg: ModelConfig, frames: jax.Array, tokens: jax.Array,
            max_len: int, rt: Runtime):
    """Encode audio, precompute int8 cross KV, run prompt through decoder."""
    enc = encode(p, cfg, frames, rt)
    B, T = tokens.shape
    state = init_decode_state(cfg, B, max_len)
    x = p["embed"]["w"][tokens]
    x = x + L.sinusoidal_positions(T, cfg.d_model).astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    hd = cfg.head_dim

    def body(xx, pl):
        h = L.apply_norm(pl["ln1"], xx)
        mix, (k, v) = A.gqa_forward(pl["attn"], cfg, h, positions, rt.backend)
        (k_q, k_s), (v_q, v_s) = KV.quantize_rows(k), KV.quantize_rows(v)
        xx = xx + mix
        hx = L.apply_norm(pl["ln_x"], xx)
        xk = L.apply_linear(L._lin(pl["xattn"], "wk"), enc, rt.backend).reshape(
            B, -1, cfg.n_kv_heads, hd)
        xv = L.apply_linear(L._lin(pl["xattn"], "wv"), enc, rt.backend).reshape(
            B, -1, cfg.n_kv_heads, hd)
        (xk_q, xk_s), (xv_q, xv_s) = KV.quantize_rows(xk), KV.quantize_rows(xv)
        xx = xx + _cross_attn(pl["xattn"], cfg, hx, (xk, xv), rt)
        xx = xx + L.apply_mlp(pl["mlp"], L.apply_norm(pl["ln2"], xx),
                              cfg.mlp_type, rt.backend)
        return xx, (k_q, k_s, v_q, v_s, xk_q, xk_s, xv_q, xv_s)

    x, caches = jax.lax.scan(body, x, p["dec"])
    k_q, k_s, v_q, v_s, xk_q, xk_s, xv_q, xv_s = caches
    for n, c in zip(("k_q", "k_s", "v_q", "v_s"), (k_q, k_s, v_q, v_s)):
        state[n] = jax.lax.dynamic_update_slice(state[n], c, (0,) * c.ndim)
    state.update(xk_q=xk_q, xk_s=xk_s, xv_q=xv_q, xv_s=xv_s)
    state["pos"] = jnp.array(T, jnp.int32)
    x = L.apply_norm(p["ln_f"], x)
    logits = jnp.einsum("bd,vd->bv", x[:, -1], p["embed"]["w"].astype(x.dtype))
    return logits, state


def decode_step(p: Params, cfg: ModelConfig, state: dict, token: jax.Array,
                rt: Runtime):
    pos = state["pos"]
    B = token.shape[0]
    x = p["embed"]["w"][token][:, None]
    x = x + _sinusoid_at(pos, cfg.d_model).astype(x.dtype)[None, None]

    def body(xx, xs):
        pl, kq, ks, vq, vs, xkq, xks, xvq, xvs = xs
        h = L.apply_norm(pl["ln1"], xx)
        mix, (kq, ks, vq, vs) = A.gqa_decode(pl["attn"], cfg, h, pos,
                                             kq, ks, vq, vs, rt.backend)
        xx = xx + mix
        hx = L.apply_norm(pl["ln_x"], xx)
        xx = xx + _cross_attn(pl["xattn"], cfg, hx, (xkq, xks, xvq, xvs), rt,
                              decode=True)
        xx = xx + L.apply_mlp(pl["mlp"], L.apply_norm(pl["ln2"], xx),
                              cfg.mlp_type, rt.backend)
        return xx, (kq, ks, vq, vs)

    x, new_kv = jax.lax.scan(body, x, (p["dec"], state["k_q"], state["k_s"],
                                       state["v_q"], state["v_s"],
                                       state["xk_q"], state["xk_s"],
                                       state["xv_q"], state["xv_s"]))
    k_q, k_s, v_q, v_s = new_kv
    x = L.apply_norm(p["ln_f"], x)
    logits = jnp.einsum("bd,vd->bv", x[:, 0], p["embed"]["w"].astype(x.dtype))
    new_state = dict(state, k_q=k_q, k_s=k_s, v_q=v_q, v_s=v_s, pos=pos + 1)
    return logits, new_state
