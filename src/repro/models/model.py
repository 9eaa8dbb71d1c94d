"""Model facade: family dispatch + input specs + FLOPs accounting."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig
from repro.models import encdec
from repro.models import transformer as T
from repro.models.transformer import Runtime

Params = dict[str, Any]


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    if cfg.family == "encdec":
        return encdec.init_params(key, cfg, dtype)
    return T.init_params(key, cfg, dtype)


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct pytree — no allocation (for the dry-run)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg=cfg, dtype=dtype),
        jax.random.key(0))


def param_bytes(cfg: ModelConfig, bytes_per_param: int = 2) -> int:
    return cfg.param_count() * bytes_per_param


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, weak-type-correct, shardable)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Model inputs for (arch x shape): tokens/labels for train, prompt for
    prefill, (token, cache-position implied by state) for decode.  The
    modality frontends ([audio]/[vlm]) are stubs: precomputed embeddings."""
    B, S = shape.global_batch, shape.seq_len
    tok = jnp.int32
    if cfg.family == "encdec":
        frames = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            return {"frames": frames,
                    "tokens": jax.ShapeDtypeStruct((B, S), tok),
                    "labels": jax.ShapeDtypeStruct((B, S), tok)}
        if shape.kind == "prefill":
            return {"frames": frames,
                    "tokens": jax.ShapeDtypeStruct((B, S), tok)}
        return {"token": jax.ShapeDtypeStruct((B,), tok)}
    if cfg.input_mode == "embeddings":
        inp = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        inp = jax.ShapeDtypeStruct((B, S), tok)
    if shape.kind == "train":
        return {"inputs": inp, "labels": jax.ShapeDtypeStruct((B, S), tok)}
    if shape.kind == "prefill":
        return {"inputs": inp}
    return {"token": jax.ShapeDtypeStruct((B,), tok)}


# ---------------------------------------------------------------------------
# MODEL_FLOPS (Sec. Roofline conventions, DESIGN.md Sec. 7)
# ---------------------------------------------------------------------------
def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    # decode: one token per sequence against a seq_len cache
    return 2.0 * n_active * shape.global_batch


# ---------------------------------------------------------------------------
# unified apply entry points
# ---------------------------------------------------------------------------
def train_loss(params, cfg: ModelConfig, batch: dict, rt: Runtime):
    if cfg.family == "encdec":
        return encdec.lm_loss(params, cfg, batch["frames"], batch["tokens"],
                              batch["labels"], rt)
    return T.lm_loss(params, cfg, batch["inputs"], batch["labels"], rt)


def prefill(params, cfg: ModelConfig, batch: dict, max_len: int, rt: Runtime,
            counts: bool = False):
    """``batch`` may carry ``lengths`` ([B] int32) for ragged right-padded
    prompts — threaded through attention masking and last-logit gathering.
    ``counts`` adds the MoE routing counters as a third output (see
    :func:`repro.models.transformer.prefill`)."""
    if cfg.family == "encdec":
        return encdec.prefill(params, cfg, batch["frames"], batch["tokens"],
                              max_len, rt)
    return T.prefill(params, cfg, batch["inputs"], max_len, rt,
                     lengths=batch.get("lengths"), counts=counts)


def init_prefill_carry(cfg: ModelConfig, buf_len: int):
    """Float K/V carry for a chunked prefill (see transformer.prefill_chunk).
    Attention-family decoders only — encdec and SSM/hybrid stacks raise and
    keep the one-shot prefill path."""
    if cfg.family == "encdec":
        raise NotImplementedError("chunked prefill targets decoder-only LMs")
    return T.init_prefill_carry(cfg, buf_len)


def warm_prefill_carry(cfg: ModelConfig, state: dict, slot, n, buf_len: int):
    """Prefix-cache warm start: seed a chunked-prefill carry from the first
    ``n`` cached rows of pool ``slot`` (see transformer.warm_prefill_carry).
    GQA attention decoders only."""
    if cfg.family == "encdec":
        raise NotImplementedError("chunked prefill targets decoder-only LMs")
    return T.warm_prefill_carry(cfg, state, slot, n, buf_len)


def prefill_chunk(params, cfg: ModelConfig, carry: dict, tokens, n_real,
                  rt: Runtime):
    """Consume ``tokens`` ([1, C], ``n_real`` of them real) at the carry's
    cursor; returns (last-real-token logits, advanced carry)."""
    if cfg.family == "encdec":
        raise NotImplementedError("chunked prefill targets decoder-only LMs")
    return T.prefill_chunk(params, cfg, carry, tokens, n_real, rt)


def finalize_prefill_carry(cfg: ModelConfig, carry: dict, max_len: int):
    """Quantize a finished carry into the B=1 decode state write_slot lands."""
    if cfg.family == "encdec":
        raise NotImplementedError("chunked prefill targets decoder-only LMs")
    return T.finalize_prefill_carry(cfg, carry, max_len)


def decode_step(params, cfg: ModelConfig, state: dict, token, rt: Runtime,
                counts: bool = False):
    if cfg.family == "encdec":
        return encdec.decode_step(params, cfg, state, token, rt)
    return T.decode_step(params, cfg, state, token, rt, counts=counts)


def multi_decode_step(params, cfg: ModelConfig, state: dict, token, m: int,
                      rt: Runtime, counts: bool = False):
    """Fused multi-step greedy decode: ``m`` decode iterations in one jitted
    scan with the argmax fed back on device -> (tokens [B, m], state).  See
    :func:`repro.models.transformer.multi_decode_step`."""
    if cfg.family == "encdec":
        raise NotImplementedError(
            "fused multi-step decode targets decoder-only LMs")
    return T.multi_decode_step(params, cfg, state, token, m, rt,
                               counts=counts)


def verify_step(params, cfg: ModelConfig, state: dict, tokens, rt: Runtime,
                depth=None, anc=None):
    """Speculative-decode verify: ``tokens`` [B, T] (last committed token +
    T-1 drafts per slot) -> (logits [B, T, V], hidden [B, T, d], state with
    ``pos + T``).  With ``depth``/``anc`` ([B, T] int32) the window is a
    draft *tree* (ancestor masking, depth positions).  Decoder-only
    attention stacks; see :func:`repro.models.transformer.verify_step`."""
    if cfg.family == "encdec":
        raise NotImplementedError("speculative decode targets decoder-only LMs")
    return T.verify_step(params, cfg, state, tokens, rt, depth=depth, anc=anc)


def tree_commit(state: dict, base, sel, keep, pos):
    """Compact a verified tree window's accepted root-path rows into
    contiguous committed rows and rewind the cursor — see
    :func:`repro.models.transformer.tree_commit`."""
    return T.tree_commit(state, base, sel, keep, pos)


def mtp_draft(params, cfg: ModelConfig, hidden, token, pos, k: int,
              rt: Runtime):
    """Draft ``k`` tokens per slot from the MTP head (requires ``cfg.mtp``)."""
    if cfg.family == "encdec":
        raise NotImplementedError("speculative decode targets decoder-only LMs")
    return T.mtp_draft(params, cfg, hidden, token, pos, k, rt)


def mtp_draft_tree(params, cfg: ModelConfig, hidden, token, pos, n: int,
                   branch: int, rt: Runtime):
    """Beam the MTP head into a static draft tree (tokens [B, n],
    chain-major node order; topology from
    :func:`repro.models.transformer.mtp_chain_lengths`)."""
    if cfg.family == "encdec":
        raise NotImplementedError("speculative decode targets decoder-only LMs")
    return T.mtp_draft_tree(params, cfg, hidden, token, pos, n, branch, rt)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.family == "encdec":
        return encdec.init_decode_state(cfg, batch, max_len)
    return T.init_decode_state(cfg, batch, max_len)
