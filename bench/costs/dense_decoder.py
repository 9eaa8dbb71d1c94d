"""What the algorithm of a dense decoder needs, from a configuration's
widths: FLOPs of a prefill and of a decode step, and bytes a decode step
must read (the count module that a configuration's ``family_module``
names).

These count the algorithm, not what the compiled program happens to do:
causal attention counts only the key rows each query sees, a decode step
reads each weight once however many slots share it, and the K/V read is
the rows in context, not the padded pool row.
"""
from __future__ import annotations


def layer_linear_params(m: dict) -> int:
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    h, g = m["n_heads"], m["n_kv_heads"]
    return d * h * hd + 2 * d * g * hd + h * hd * d + 3 * d * ff


def layer_out_features(m: dict) -> int:
    """Output channels of one layer's linears (one int8 scale each)."""
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    return m["n_heads"] * hd + 2 * m["n_kv_heads"] * hd + d + 2 * ff + d


def param_count(m: dict) -> int:
    """Embedding, head (untied) and every layer's linears and norm gains."""
    d, v = m["d_model"], m["vocab_size"]
    per_layer = layer_linear_params(m) + 2 * d
    return v * d * (1 if m["tie_embeddings"] else 2) + m["n_layers"] * per_layer


def _attn_flops(m: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs in every layer."""
    return m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * pairs


def prefill_flops(m: dict, n: int) -> int:
    """An ``n``-token prompt: every linear per token, causal attention
    (n(n+1)/2 pairs), and the head for the last token only."""
    lin = m["n_layers"] * layer_linear_params(m)
    return (2 * n * lin + _attn_flops(m, n * (n + 1) // 2)
            + 2 * m["d_model"] * m["vocab_size"])


def decode_flops(m: dict, ctx: int) -> int:
    """One token whose query sees ``ctx`` key rows (itself included)."""
    lin = m["n_layers"] * layer_linear_params(m)
    return 2 * (lin + m["d_model"] * m["vocab_size"]) + _attn_flops(m, ctx)


def decode_weight_bytes(m: dict) -> int:
    """Weights one decode step reads once, whatever the batch: int8
    linears with a float32 scale per output channel, the bf16 head (the
    embedding when tied), float32 norm gains, and one bf16 embedding row
    per slot counted with the K/V (negligible)."""
    d, v, n = m["d_model"], m["vocab_size"], m["n_layers"]
    lin = n * (layer_linear_params(m) + 4 * layer_out_features(m))
    return lin + 2 * d * v + 4 * d * (2 * n + 1)


def kv_bytes(m: dict, ctx: int) -> int:
    """K/V rows one slot's decode step reads: ``ctx`` rows of int8 K and V
    per KV head, each with a float32 scale, in every layer."""
    per_row = m["n_kv_heads"] * 2 * (m["head_dim"] + 4)
    return m["n_layers"] * ctx * per_row
