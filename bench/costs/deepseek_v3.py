"""What the algorithm of a DeepSeek-V3 stack needs, from a configuration's
widths: parameters, FLOPs of a prefill and of a decode token, and bytes a
decode step must read (the count module that a configuration's
``family_module`` names).

Routed experts are counted from the program's MoE counters and not from
the widths: FLOPs per routed (token, expert) pair that landed on an expert
held here (:func:`expert_pair_flops`), and bytes per held expert that a
decode step touched (:func:`expert_bytes`).  Everything else counts the
algorithm, as :mod:`bench.costs.dense_decoder` does: causal attention only
over the key rows each query sees (prefill in MLA's expanded form, decode
absorbed into the 512-dim latent), each weight read once per decode step
however many slots share it, and the latent rows in context, not the
padded pool row.
"""
from __future__ import annotations


def moe_layers(m: dict) -> int:
    return m["n_layers"] - m["first_dense_layers"] if m["n_experts"] else 0


def held(m: dict) -> int:
    return m["n_experts_held"] or m["n_experts"]


def attn_params(m: dict) -> int:
    """MLA linears of one layer (``wkv_b`` included)."""
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    ql = m["q_lora_rank"]
    return (d * ql + ql * h * (dn + dr) + d * (r + dr)
            + r * h * (dn + dv) + h * dv * d)


def attn_out_features(m: dict) -> int:
    """Output channels of one layer's MLA linears (one int8 scale each)."""
    h, r = m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (m["q_lora_rank"] + h * (dn + dr) + r + dr + h * (dn + dv)
            + m["d_model"])


def expert_params(m: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def shared_params(m: dict) -> int:
    return m["n_shared_experts"] * expert_params(m)


def router_params(m: dict) -> int:
    return m["n_experts"] * (m["d_model"] + (1 if m["moe_router_bias"]
                                             else 0))


def param_count(m: dict) -> int:
    """Embedding, untied head, and every layer's linears, router, held
    experts and norm gains (``ln1``, ``ln2``), as the program counts."""
    d, v = m["d_model"], m["vocab_size"]
    f, e = m["first_dense_layers"], moe_layers(m)
    dense = attn_params(m) + 3 * d * m["d_ff"] + 2 * d
    moe = (attn_params(m) + router_params(m) + shared_params(m)
           + held(m) * expert_params(m) + 2 * d)
    return 2 * v * d + f * dense + e * moe


def dense_token_params(m: dict) -> int:
    """Weights every token multiplies through, routed experts aside."""
    d = m["d_model"]
    return (m["n_layers"] * attn_params(m)
            + m["first_dense_layers"] * 3 * d * m["d_ff"]
            + moe_layers(m) * (shared_params(m) + d * m["n_experts"]))


def expert_pair_flops(m: dict) -> int:
    """One token through one routed expert."""
    return 2 * expert_params(m)


def prefill_flops(m: dict, n: int) -> int:
    """An ``n``-token prompt, routed experts aside: every other linear per
    token, causal MLA attention in its expanded form (n(n+1)/2 pairs, a
    (nope + rope)-dim score and a v-dim output per head), and the head
    for the last token only."""
    h = m["n_heads"]
    per_pair = 2 * h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                        + m["v_head_dim"])
    return (2 * n * dense_token_params(m)
            + m["n_layers"] * per_pair * (n * (n + 1) // 2)
            + 2 * m["d_model"] * m["vocab_size"])


def decode_flops(m: dict, ctx: int) -> int:
    """One token whose query sees ``ctx`` latent rows (itself included),
    routed experts aside: absorbed MLA scores each row over the latent and
    rope dims and sums it over the latent, per head (``wkv_b``'s absorb
    and expand count as its linear)."""
    r, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    attn = m["n_layers"] * 2 * m["n_heads"] * (2 * r + dr) * ctx
    return (2 * (dense_token_params(m) + m["d_model"] * m["vocab_size"])
            + attn)


def expert_bytes(m: dict) -> int:
    """One held expert read at decode: int8 weights, a float32 scale per
    output channel (weight-only int8 stacks keep bf16 scales: 2 bytes)."""
    d, ff = m["d_model"], m["moe_d_ff"]
    return expert_params(m) + 2 * (2 * ff + d)


def decode_fixed_bytes(m: dict) -> int:
    """What every decode step reads once, routed experts aside: int8
    linears with a float32 scale per output channel (the shared expert's
    scales bf16, as the routed), float32 router and bias, the bf16 head,
    float32 norm gains."""
    d, v, n = m["d_model"], m["vocab_size"], m["n_layers"]
    f, e = m["first_dense_layers"], moe_layers(m)
    ff = m["d_ff"]
    attn = attn_params(m) + 4 * attn_out_features(m)
    dense = 3 * d * ff + 4 * (2 * ff + d)
    shared = m["n_shared_experts"] * expert_bytes(m)
    norms = 4 * (2 * d + m["q_lora_rank"] + m["kv_lora_rank"])
    return (n * (attn + norms) + f * dense
            + e * (shared + 4 * router_params(m)) + 2 * d * v + 4 * d)


def latent_bytes(m: dict, ctx: int) -> int:
    """Latent rows one slot's decode step reads: ``ctx`` int8 rows of
    kv_lora + rope, each with a float32 scale, in every layer."""
    return m["n_layers"] * ctx * (m["kv_lora_rank"] + m["qk_rope_head_dim"]
                                  + 4)
