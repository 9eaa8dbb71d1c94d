"""Sharding specs for the production meshes.

Mesh contract (see ``launch/mesh.py``): every mesh has a ``model`` axis
(tensor/expert parallelism — the intra-die dimension where the H-tree
combines partial sums) and one or more *data* axes (``data``, optionally a
leading ``pod``) over which batches, decode slots and FSDP-stored weights
shard.  ``data_axes(mesh)`` is simply "every axis that is not ``model``",
so the same specs drive the 2-D ``(data, model)`` and 3-D
``(pod, data, model)`` meshes.

Param layout follows the Megatron split: column-parallel projections
(``wq/wk/wv/w_up/...``) shard their output dim over ``model`` and FSDP
their input dim over the data axes; row-parallel projections
(``wo/w_down/out_proj``) do the transpose.  Quantized "QLC" weights
(``*_q``) shard like their float originals and their per-output-column
scales (``*_s``) ride the output dim's axes.

MoE weights get their own treatment (:func:`moe_param_specs`) because the
paper's store-and-compute rule makes decode experts *resident*: they never
migrate, tokens come to them.  Three resident layouts cover the assigned
archs (:func:`moe_serve_strategy`):

* ``ep2``  — experts sharded over data x model jointly (plenty of experts,
  e.g. DeepSeek's 256);
* ``ep_data`` — experts sharded over the data axes, expert FFN dim
  tensor-sliced over ``model`` (few experts, e.g. Grok's 8);
* ``etp2`` — every expert on every device, FFN dim sliced over *all* axes
  (experts don't divide the data axes but the FFN dim divides the mesh).

Training/prefill instead use ``ep``/``etp`` over ``model`` with ZeRO-3
style FSDP storage over the data axes (gathered transiently per layer
inside ``_moe_block``).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig

MODEL_AXIS = "model"

# column-parallel: output dim over `model`, input dim FSDP over data axes
_COL_PARALLEL = {"wq", "wk", "wv", "wq_a", "wq_b", "wkv_a", "wkv_b",
                 "w_up", "w_gate", "w_z", "w_x"}
# row-parallel: input dim over `model`, output dim FSDP over data axes
_ROW_PARALLEL = {"wo", "w_down", "out_proj"}


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= x
    return out


def data_axes(mesh) -> tuple[str, ...]:
    """Every mesh axis that is not the model axis (``pod``/``data``/...)."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def axes_size(mesh, axes) -> int:
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    return _prod(mesh.shape[a] for a in axes)


def _fit(mesh, dim: int, axes):
    """``axes`` if they evenly tile ``dim``, else None (replicate)."""
    if axes is None:
        return None
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not axes:
        return None
    total = axes_size(mesh, axes)
    if dim % total == 0 and dim >= total:
        return axes if len(axes) > 1 else axes[0]
    return None


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_entry(global_batch: int, mesh):
    """PartitionSpec entry for a leading batch/slot dim: the (combined) data
    axes when they tile the batch, else None."""
    return _fit(mesh, global_batch, data_axes(mesh))


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------
def input_shardings(cfg: ModelConfig, shape: ShapeConfig, specs: dict,
                    mesh) -> dict:
    """Batch-shard every model input over the data axes (dim 0)."""
    b = batch_entry(shape.global_batch, mesh)
    out = {}
    for k, v in specs.items():
        out[k] = NamedSharding(mesh, P(b, *([None] * (v.ndim - 1))))
    return out


# ---------------------------------------------------------------------------
# MoE strategies
# ---------------------------------------------------------------------------
def moe_serve_strategy(cfg: ModelConfig, mesh) -> str:
    """Pick the resident-expert decode layout for (cfg, mesh).

    Falls back to the training-style ``ep``/``etp`` tag when no resident
    layout tiles the mesh (``_moe_block`` then keeps the FSDP-gather path).
    """
    dp = data_axes(mesh)
    dp_total = axes_size(mesh, dp)
    m_size = mesh.shape[MODEL_AXIS]
    total = dp_total * m_size
    E, ff = cfg.n_experts, cfg.moe_d_ff
    if E and E % total == 0 and E >= total:
        return "ep2"
    if (E and dp_total > 1 and E % dp_total == 0 and E >= dp_total
            and ff % m_size == 0):
        return "ep_data"
    if ff and ff % total == 0:
        return "etp2"
    return _moe_train_strategy(cfg, mesh)


def _moe_train_strategy(cfg: ModelConfig, mesh) -> str:
    m_size = mesh.shape[MODEL_AXIS]
    if cfg.n_experts % m_size == 0 and cfg.n_experts >= m_size:
        return "ep"
    if cfg.moe_d_ff % m_size == 0:
        return "etp"
    raise ValueError(
        f"no MoE layout tiles model axis {m_size}: n_experts="
        f"{cfg.n_experts}, moe_d_ff={cfg.moe_d_ff} ({cfg.name})")


def _shared_specs(cfg: ModelConfig, mesh) -> dict:
    """Shared-expert MLP: FFN dim tensor-sliced over `model` only (the
    combine psums over `model` in every strategy; data-axis replication is
    pre-scaled by ``shared_scale`` in ``moe_local``)."""
    if not cfg.n_shared_experts:
        return {}
    ffs = cfg.moe_d_ff * cfg.n_shared_experts
    m_size = mesh.shape[MODEL_AXIS]
    if ffs % m_size != 0 and m_size > 1:
        raise ValueError(
            f"shared-expert FFN {ffs} does not tile model axis {m_size}")
    m = _fit(mesh, ffs, MODEL_AXIS)
    return {
        "w_up": P(None, m), "w_gate": P(None, m), "w_down": P(m, None),
        "w_up_q": P(None, m), "w_gate_q": P(None, m), "w_down_q": P(m, None),
        "w_up_s": P(m), "w_gate_s": P(m), "w_down_s": P(None),
    }


def moe_param_specs(cfg: ModelConfig, mesh, serve: bool = False) -> dict:
    """PartitionSpecs for one (unstacked) MoE layer's params.

    Returns ``{"strategy", "ep_axes", "spec", "shared", "gather"}`` —
    consumed by ``transformer._moe_block`` as shard_map in_specs (``spec``,
    ``shared``), expert-placement axes (``ep_axes``), and per-name FSDP
    gather dims (``gather``, train/prefill only).
    """
    dp = data_axes(mesh)
    m = MODEL_AXIS
    all_ax = dp + (m,)
    E, ff, d = cfg.n_experts, cfg.moe_d_ff, cfg.d_model
    strategy = (moe_serve_strategy(cfg, mesh) if serve
                else _moe_train_strategy(cfg, mesh))

    def expert(e=None, din=None, dout=None, s_out=None):
        """Specs for the (w_up|w_gate, w_down, scales) family given the
        axes of the expert dim, the FFN-in/out dims and the scale dim."""
        return {
            "w_up": P(e, din, dout), "w_gate": P(e, din, dout),
            "w_up_q": P(e, din, dout), "w_gate_q": P(e, din, dout),
            "w_up_s": P(e, s_out), "w_gate_s": P(e, s_out),
            "w_down": P(e, dout, din), "w_down_q": P(e, dout, din),
            "w_down_s": P(e, None),
            "router": P(None, None),
        }

    gather: dict[str, int] = {}
    if strategy == "ep2":
        ep_axes = all_ax
        spec = expert(e=_fit(mesh, E, all_ax))
    elif strategy == "ep_data":
        ep_axes = dp
        spec = expert(e=_fit(mesh, E, dp), dout=_fit(mesh, ff, m),
                      s_out=_fit(mesh, ff, m))
    elif strategy == "etp2":
        ep_axes = all_ax
        spec = expert(dout=_fit(mesh, ff, all_ax),
                      s_out=_fit(mesh, ff, all_ax))
    elif strategy == "ep":
        ep_axes = (m,)
        fs = _fit(mesh, d, dp)        # ZeRO-3 store: d_model FSDP-sharded
        ffs = _fit(mesh, ff, dp)
        spec = expert(e=m, din=fs)
        spec["w_down"] = P(m, ffs, None)
        spec["w_down_q"] = P(m, ffs, None)
        spec["w_down_s"] = P(m, None)
        spec["w_up_s"] = spec["w_gate_s"] = P(m, None)
        if fs is not None:
            gather.update({"w_up": 1, "w_gate": 1})
        if ffs is not None:
            gather["w_down"] = 1
    else:                             # etp: all experts local, FFN over model
        ep_axes = (m,)
        spec = expert(dout=_fit(mesh, ff, m), s_out=_fit(mesh, ff, m))
    return {"strategy": strategy, "ep_axes": ep_axes, "spec": spec,
            "shared": _shared_specs(cfg, mesh), "gather": gather}


# ---------------------------------------------------------------------------
# whole-model param shardings
# ---------------------------------------------------------------------------
def _linear_name(path_keys: list[str]) -> str:
    """Resolve the linear a leaf belongs to: ``{"wq": ...}`` names itself;
    ``{"lm_head": {"w": ...}}`` is named by its parent dict."""
    leaf = path_keys[-1]
    base = leaf[:-2] if leaf.endswith(("_q", "_s")) else leaf
    if base == "w" and len(path_keys) >= 2:
        return path_keys[-2]
    return base


def _pad(entries, ndim: int):
    """Left-pad a spec with None for stacked leading dims (layer scan)."""
    if len(entries) > ndim:
        return None
    return P(*([None] * (ndim - len(entries)) + list(entries)))


def param_shardings(cfg: ModelConfig, params_abs: Any, mesh,
                    serve: bool = False):
    """NamedSharding pytree matching ``params_abs`` (float or quantized)."""
    dp = data_axes(mesh)
    m = MODEL_AXIS
    moe = moe_param_specs(cfg, mesh, serve=serve) if cfg.n_experts else None

    def spec_for(path_keys: list[str], x) -> P:
        leaf = path_keys[-1]
        if moe is not None and "moe" in path_keys:
            table = moe["shared"] if "shared" in path_keys else moe["spec"]
            got = table.get(leaf)
            if got is not None:
                padded = _pad(tuple(got), x.ndim)
                if padded is not None:
                    return padded
            return P()
        name = _linear_name(path_keys)
        scale = leaf.endswith("_s")
        if name == "embed" and x.ndim >= 2:
            return _pad((_fit(mesh, x.shape[-2], m),
                         _fit(mesh, x.shape[-1], dp)), x.ndim)
        if name in ("lm_head", "mtp_proj") and not scale and x.ndim >= 2:
            return _pad((_fit(mesh, x.shape[-2], dp),
                         _fit(mesh, x.shape[-1], m)), x.ndim)
        if name in _COL_PARALLEL:
            if scale:
                return _pad((_fit(mesh, x.shape[-1], m),), x.ndim)
            if x.ndim >= 2:
                return _pad((_fit(mesh, x.shape[-2], dp),
                             _fit(mesh, x.shape[-1], m)), x.ndim)
        if name in _ROW_PARALLEL:
            if scale:
                return _pad((_fit(mesh, x.shape[-1], dp),), x.ndim)
            if x.ndim >= 2:
                return _pad((_fit(mesh, x.shape[-2], m),
                             _fit(mesh, x.shape[-1], dp)), x.ndim)
        return P()                       # norms, router, SSM controller ops

    def walk(node, path_keys):
        if isinstance(node, dict):
            return {k: walk(v, path_keys + [k]) for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            return type(node)(walk(v, path_keys) for v in node)
        return NamedSharding(mesh, spec_for(path_keys, node))

    return walk(params_abs, [])


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------
def prefill_carry_shardings(cfg: ModelConfig, carry_abs: Any, mesh):
    """Chunked-prefill carry (B=1 float K/V + cursor): the chunk batch is a
    single request, so nothing shards over the data axes — leaves replicate
    there — while attention heads (dim 3 of the 5-dim ``[n_p, 1, S_buf, H,
    D]`` buffers) shard over ``model`` when they tile it, mirroring the
    slot pool's head sharding so the finalize -> ``write_slot`` handoff
    never reshards.  ``pos`` and low-rank (MLA latent) leaves replicate."""

    def leaf_sharding(path_keys, x):
        if "pos" in path_keys or x.ndim < 5:
            return replicated(mesh)
        entries = [None] * x.ndim
        entries[3] = _fit(mesh, x.shape[3], MODEL_AXIS)
        return NamedSharding(mesh, P(*entries))

    def walk(node, path_keys):
        if isinstance(node, dict):
            return {k: walk(v, path_keys + [k]) for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            return type(node)(walk(v, path_keys) for v in node)
        return leaf_sharding(path_keys, node)

    return walk(carry_abs, [])


def serve_step_shardings(n_slots: int, mesh) -> dict:
    """Decode-lane I/O shardings for the jitted serve steps, pinned beside
    the slot pool so every step's in/out layouts match — which is also what
    lets ``donate_argnums`` alias the donated pool buffers in place (XLA
    only aliases a donated input whose layout equals the output's):

    * ``tokens``  — the [B] last-token vector fed to ``decode_step``;
    * ``block``   — the [B, m] fused multi-step token block (and the [B, k]
      device-side top-k indices/values the sampled path fetches instead of
      full-vocab rows);
    * ``logits``  — the [B, V] decode logits (stay device-resident; only
      argmax / top-k products cross to the host).

    The slot axis shards over the data axes; vocab / window dims replicate.
    """
    b = batch_entry(n_slots, mesh)
    return {
        "tokens": NamedSharding(mesh, P(b)),
        "block": NamedSharding(mesh, P(b, None)),
        "logits": NamedSharding(mesh, P(b, None)),
    }


def verify_shardings(n_slots: int, mesh) -> dict:
    """Speculative verify-step I/O shardings, pinned like the decode pool:
    the slot axis of the [B, T] draft tokens, [B, T, V] logits and
    [B, T, d] hidden carry shards over the data axes (T — the verify
    window — and vocab/model dims replicate).  Pinning these beside the
    pool's ``decode_state_shardings`` keeps the jitted verify step from
    migrating the SLC pool on any draft-length path."""
    b = batch_entry(n_slots, mesh)
    return {
        "tokens": NamedSharding(mesh, P(b, None)),
        "logits": NamedSharding(mesh, P(b, None, None)),
        "hidden": NamedSharding(mesh, P(b, None, None)),
    }


def tree_verify_shardings(n_slots: int, mesh) -> dict:
    """Tree-verify extras, pinned beside :func:`verify_shardings`: the
    [B, T] per-slot node depths and ancestor bitmasks shard their slot axis
    over the data axes like the draft tokens (the mask rides the same rows
    of the window), while the tree-commit operands replicate — they feed
    per-slot dynamic slicing inside the jitted path gather, exactly like
    the prefix-cache admission scalars:

    * ``window`` — depth / anc [B, T] int32 (slot axis data-sharded);
    * ``commit`` — base [B], sel [B, W], keep [B], pos [B] (replicated,
      matching the pool's replicated ``pos`` leaf the new cursor lands in).
    """
    b = batch_entry(n_slots, mesh)
    return {
        "window": NamedSharding(mesh, P(b, None)),
        "commit": replicated(mesh),
    }


def prefix_gather_shardings(mesh) -> dict:
    """Prefix-cache admission I/O, pinned beside the pool: the row gather
    (``transformer.copy_slot_prefix``) and the warm-carry dequant take the
    pool at ``decode_state_shardings`` in *and* out — the donation-alias
    condition, and what keeps a warm admission from migrating slot rows so
    meshed serve stays token-identical to single-device — while the scalar
    operands (source/destination slot ids, matched row count) replicate:

    * ``slot`` — src/dst slot ids (host scalars, feed dynamic slicing);
    * ``rows`` — the matched prefix length (masks the copied rows).

    The source and destination rows may live on different data-axis shards
    (the slot axis is data-sharded); XLA lowers the cross-shard row move
    inside the jitted gather, so no host round-trip ever touches the rows.
    """
    r = replicated(mesh)
    return {"slot": r, "rows": r}


def swap_row_shardings(mesh) -> dict:
    """Tiered-pool swap I/O, pinned beside the pool: ``read_slot`` (the
    swap-out gather) takes the pool at ``decode_state_shardings`` in and
    replicates its batch=1 row tree out — the row crosses to the host
    anyway, so a replicated output makes the explicit ``device_get`` a
    single-shard fetch instead of an all-gather per leaf.  The slot-id
    scalar replicates (it feeds dynamic slicing), and swap-*in* pushes the
    restored row replicated too, landing through the same pinned
    ``write_slot`` admissions use — so swap restores never migrate the
    pool and meshed swap-resume stays token-identical to single-device.

    * ``slot`` — the slot id scalar;
    * ``row``  — the batch=1 row tree (out of ``read_slot``, into the
      engine's ``_push`` on swap-in).
    """
    r = replicated(mesh)
    return {"slot": r, "row": r}


def kv_pool_spec(n_slots: int, n_kv_heads: int, mesh) -> dict:
    """PartitionSpecs of the GQA pool leaves, as
    :func:`decode_state_shardings` lays them: slots over the data axes;
    the lane-dense ``[n_p, B, S, H_kv·D]`` int8 rows split their merged
    axis, and the ``[n_p, B, S, H_kv, 1]`` scales their KV-head axis, over
    `model` when the KV heads tile it, so each device holds whole KV-head
    groups."""
    b = batch_entry(n_slots, mesh)
    m = _fit(mesh, n_kv_heads, MODEL_AXIS)
    rows, scales = P(None, b, None, m), P(None, b, None, m, None)
    return {"k_q": rows, "k_s": scales, "v_q": rows, "v_s": scales}


def decode_state_shardings(cfg: ModelConfig, shape: ShapeConfig,
                           state_abs: Any, mesh):
    """Slot-pool decode state: the batch/slot axis (dim 1 of every cache
    leaf, under the layer-stack dim) shards over the data axes.  The
    lane-dense int8 K/V rows (told by name: ``k_q``/``v_q``, and the
    encoder-decoder's cross-attention ``xk_q``/``xv_q``) split their merged
    ``H_kv·D`` axis over `model` when the KV heads tile it, as
    :func:`kv_pool_spec` lays them; 5-dim leaves (the rows' per-head
    scales, SSM state) shard dim 3 likewise.  ``pos`` and other per-slot
    scalars replicate (they feed control flow)."""
    b = batch_entry(shape.global_batch, mesh)

    def leaf_sharding(path_keys, x):
        if "pos" in path_keys or x.ndim < 2:
            return replicated(mesh)
        entries = [None] * x.ndim
        if x.shape[1] == shape.global_batch:
            entries[1] = b
        if path_keys[-1] in ("k_q", "v_q", "xk_q", "xv_q"):
            entries[3] = _fit(mesh, cfg.n_kv_heads, MODEL_AXIS)
        elif x.ndim == 5:
            entries[3] = _fit(mesh, x.shape[3], MODEL_AXIS)
        return NamedSharding(mesh, P(*entries))

    def walk(node, path_keys):
        if isinstance(node, dict):
            return {k: walk(v, path_keys + [k]) for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            return type(node)(walk(v, path_keys) for v in node)
        return leaf_sharding(path_keys, node)

    return walk(state_abs, [])
