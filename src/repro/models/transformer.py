"""Decoder-LM assembly for every assigned family (dense/MoE/SSM/hybrid/VLM).

Layers are stacked and scanned for compile-time compactness.  Heterogeneous
stacks (Jamba's 7:1 Mamba:attention interleave with alternating MoE) scan
over *periods*: the smallest repeating structural unit, with the slots inside
a period unrolled.  DeepSeek's dense prefix + MoE tail is two groups.

The decode path is the paper's technique: every static linear can run W8A8
("QLC region"), attention runs against the int8 "SLC" cache, and norms,
softmax, and routing are fp32 "controller ops".
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import kvcache as KV
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution context threaded through model apply functions."""
    backend: str = "dense"               # dense | ref_int8 | fused_int8 | pim_bitserial
    mesh: Any = None                     # jax.sharding.Mesh | None
    data_axes: tuple = ("data",)
    model_axis: str = "model"
    remat: bool = False
    collective: str = "psum"             # psum (ring) | htree (tree all-reduce)
    serve_resident_moe: bool = False     # decode: experts resident (no FSDP gather)
    dmvm_dtype: Any = None               # e.g. jnp.bfloat16 for SLC intermediates
    seq_shard: bool = False              # sequence-parallel activations (train)


def tree_stack(trees: list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# layer structure
# ---------------------------------------------------------------------------
def structure_key(cfg: ModelConfig, i: int) -> tuple:
    return (cfg.layer_kind(i), cfg.is_moe_layer(i))


def layer_groups(cfg: ModelConfig) -> list[tuple[int, int, int]]:
    """[(start, count, period)] covering all decoder layers."""
    n = cfg.n_layers
    if cfg.family == "hybrid":
        p = cfg.attn_every
        assert n % p == 0
        return [(0, n, p)]
    if cfg.first_dense_layers:
        f = cfg.first_dense_layers
        return [(0, f, 1), (f, n - f, 1)]
    return [(0, n, 1)]


def init_layer(key, cfg: ModelConfig, i: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    kind = cfg.layer_kind(i)
    p: Params = {"ln1": L.norm_init(cfg.d_model, cfg.norm_type)}
    if kind == "ssm":
        p["ssm"] = S.ssm_init(ks[0], cfg, dtype)
    else:
        p["attn"] = A.attn_init(ks[0], cfg, dtype)
    if cfg.is_moe_layer(i):
        p["ln2"] = L.norm_init(cfg.d_model, cfg.norm_type)
        p["moe"] = M.moe_init(ks[1], cfg, dtype)
    elif cfg.d_ff:
        p["ln2"] = L.norm_init(cfg.d_model, cfg.norm_type)
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _moe_block(p: Params, x: jax.Array, cfg: ModelConfig, rt: Runtime):
    if rt.mesh is None:
        return M.moe_apply(p, x, cfg, axis_name=None)

    from repro.dist import collectives as C
    from repro.dist import sharding as SH
    ms = SH.moe_param_specs(cfg, rt.mesh, serve=rt.serve_resident_moe)
    dp = SH.data_axes(rt.mesh)
    dp_total = 1
    for a in dp:
        dp_total *= rt.mesh.shape[a]
    # resident layouts replicate tokens inside the block — decode-only
    # (T==1); prefill/train keep batch-sharded activations + FSDP gathers
    resident = ms["strategy"] in ("ep2", "ep_data", "etp2") and x.shape[1] == 1
    if not resident and rt.serve_resident_moe:
        ms = SH.moe_param_specs(cfg, rt.mesh, serve=False)
    if resident:
        # tokens replicated inside the block; experts never move (the paper's
        # store-and-compute rule: decode weights are flash-resident)
        x_spec = P(None, None, None)
    else:
        b_entry = dp if (x.shape[0] % dp_total == 0 and x.shape[0] >= dp_total) else None
        x_spec = P(b_entry, None, None)

    def spec_for(nm, leaf):
        key = nm[:-2] if nm.endswith("_q") else nm
        if nm in ms["spec"]:
            return ms["spec"][nm]
        if key in ms["spec"]:
            return ms["spec"][key]
        return P(*([None] * leaf.ndim))

    pspec = {}
    for nm, leaf in p.items():
        if nm == "shared":
            pspec[nm] = {k: ms["shared"].get(k, P(*([None] * leaf[k].ndim)))
                         for k in leaf}
        else:
            pspec[nm] = spec_for(nm, leaf)

    if resident:
        ep_axes = ms["ep_axes"]

        def f(pp, xx):
            B, T, d = xx.shape
            xf = xx.reshape(B * T, d)
            if ms["strategy"] == "etp2":
                # all experts local, FFN sliced over every axis
                e_first, n_local = 0, cfg.n_experts
            else:
                size = 1
                idx = jnp.zeros((), jnp.int32)
                for a in ep_axes:
                    idx = idx * rt.mesh.shape[a] + jax.lax.axis_index(a)
                    size *= rt.mesh.shape[a]
                n_local = cfg.n_experts // size
                e_first = idx * n_local
            # shared experts are ff-sliced over model but replicated over the
            # data axes, which the combine psums over -> pre-scale
            out, aux = M.moe_local(pp, xf, cfg, e_first=e_first,
                                   n_local=n_local,
                                   shared_scale=1.0 / dp_total)
            axes = tuple(ep_axes) + ((rt.model_axis,) if ms["strategy"] == "ep_data"
                                     else ())
            if rt.collective == "htree":
                for a in axes:          # log-depth tree reduce per axis
                    out = C.htree_allreduce(out, a)
            else:
                out = jax.lax.psum(out, axes)
            aux = jax.lax.pmean(aux, tuple(rt.mesh.axis_names))
            return out.reshape(B, T, d).astype(xx.dtype), aux
    else:
        def f(pp, xx):
            # FSDP: expert weights store data-sharded; gather the FSDP dim here
            # (transient, one layer at a time under the scan — the ZeRO-3 pattern)
            pp = dict(pp)
            if dp:
                for nm in list(pp):
                    key = nm[:-2] if nm.endswith("_q") else nm
                    ax_g = ms["gather"].get(nm, ms["gather"].get(key))
                    if nm != "shared" and ax_g is not None:
                        pp[nm] = jax.lax.all_gather(pp[nm], dp, axis=ax_g,
                                                    tiled=True)
            out, aux = M.moe_apply(pp, xx, cfg, axis_name=rt.model_axis,
                                   reduce_fn=lambda o: C.allreduce(
                                       o, rt.model_axis, rt.collective))
            aux = jax.lax.pmean(aux, tuple(rt.mesh.axis_names))
            return out, aux

    out, aux = jax.shard_map(f, mesh=rt.mesh, in_specs=(pspec, x_spec),
                             out_specs=(x_spec, P()), check_vma=False)(p, x)
    return out, aux


def zero_counts() -> dict:
    """MoE routing counters before any layer (see
    :func:`repro.models.moe.moe_local_counted`)."""
    return {"local_pairs": jnp.zeros((), jnp.int32),
            "experts_touched": jnp.zeros((), jnp.int32)}


def _ffn(p: Params, x: jax.Array, cfg: ModelConfig, rt: Runtime,
         counts: dict | None, valid=None) -> tuple[jax.Array, dict | None]:
    """The residual's second half of a decode or prefill layer: the MoE
    block or the MLP.  Unless ``counts`` is None, one chip's MoE share
    (no mesh) adds its routing counters of the ``valid`` rows to it."""
    if "moe" in p:
        h = L.apply_norm(p["ln2"], x, cfg.norm_eps)
        if counts is None or rt.mesh is not None:
            return x + _moe_block(p["moe"], h, cfg, rt)[0], counts
        mo, _, c = M.moe_share(p["moe"], h, cfg, valid)
        return x + mo, jax.tree.map(jnp.add, counts, c)
    if "mlp" in p:
        x = x + L.apply_mlp(p["mlp"], L.apply_norm(p["ln2"], x, cfg.norm_eps),
                            cfg.mlp_type, rt.backend)
    return x, counts


def apply_layer_train(p: Params, cfg: ModelConfig, slot: int, x, positions,
                      rt: Runtime):
    kind = cfg.layer_kind(slot)
    h = L.apply_norm(p["ln1"], x, cfg.norm_eps)
    if kind == "ssm":
        mix = S.ssm_forward(p["ssm"], cfg, h, backend=rt.backend)
    elif cfg.attn_type == "mla":
        mix, _ = A.mla_forward(p["attn"], cfg, h, positions, rt.backend)
    else:
        mix, _ = A.gqa_forward(p["attn"], cfg, h, positions, rt.backend)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        h2 = L.apply_norm(p["ln2"], x, cfg.norm_eps)
        mo, aux = _moe_block(p["moe"], h2, cfg, rt)
        x = x + mo
    elif "mlp" in p:
        h2 = L.apply_norm(p["ln2"], x, cfg.norm_eps)
        x = x + L.apply_mlp(p["mlp"], h2, cfg.mlp_type, rt.backend)
    return x, aux


# ---------------------------------------------------------------------------
# whole-model params
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8 + len(layer_groups(cfg)))
    p: Params = {"embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
                 "ln_f": L.norm_init(cfg.d_model, cfg.norm_type)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dtype)
    groups = []
    for gi, (start, count, period) in enumerate(layer_groups(cfg)):
        gkeys = jax.random.split(ks[2 + gi], count)
        # one batched init per period slot builds the stacked [n_p, ...]
        # leaves directly — no per-layer copies held for a stack (every
        # layer of a slot shares its structure, see layer_groups)
        slots = tuple(
            jax.vmap(lambda k, s=s: init_layer(k, cfg, start + s, dtype))(
                gkeys[s::period])
            for s in range(period))
        groups.append(slots)
    p["groups"] = tuple(groups)
    if cfg.mtp:
        p["mtp_proj"] = L.dense_init(ks[6], 2 * cfg.d_model, cfg.d_model, dtype)
        p["mtp_layer"] = init_layer(ks[7], cfg, cfg.n_layers - 1, dtype)
    return p


def _embed(p: Params, cfg: ModelConfig, inputs: jax.Array, pos_offset=0) -> jax.Array:
    if cfg.input_mode == "embeddings" and inputs.ndim == 3:
        x = inputs
    else:
        x = p["embed"]["w"][inputs]
    if not cfg.rope_theta:                               # sinusoidal positions
        pe = L.sinusoidal_positions(x.shape[1], cfg.d_model, pos_offset)
        x = x + pe.astype(x.dtype)
    return x


def _lm_head(p: Params, cfg: ModelConfig, h: jax.Array, rt: Runtime) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", h, p["embed"]["w"].astype(h.dtype))
    return L.apply_linear(L._lin(p["lm_head"], "w"), h, rt.backend)


def forward_train(p: Params, cfg: ModelConfig, inputs: jax.Array,
                  rt: Runtime) -> tuple[jax.Array, jax.Array]:
    """inputs: [B, T] int tokens (or [B, T, d] embeddings).
    Returns (hidden [B, T, d], aux_loss)."""
    x = _embed(p, cfg, inputs)
    B, T = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    aux_total = jnp.zeros((), jnp.float32)
    for (start, count, period), slots in zip(layer_groups(cfg), p["groups"]):
        def body(carry, slot_trees):
            xx, aux = carry
            for s in range(period):
                xx, a = apply_layer_train(slot_trees[s], cfg, start + s, xx,
                                          positions, rt)
                aux = aux + a
            if rt.seq_shard and rt.mesh is not None:
                # Megatron-style sequence parallelism: residuals/norms live
                # sequence-sharded over the model axis between layers
                from jax.sharding import NamedSharding
                xx = jax.lax.with_sharding_constraint(
                    xx, NamedSharding(rt.mesh,
                                      P(rt.data_axes, rt.model_axis, None)))
            return (xx, aux), None
        body_fn = jax.checkpoint(body) if rt.remat else body
        (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total), slots)
    x = L.apply_norm(p["ln_f"], x, cfg.norm_eps)
    return x, aux_total


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Nested cache pytree mirroring the group/slot structure.

    ``pos`` is a [B] vector: each batch row is an independent *slot* whose
    sequence position advances on its own (continuous batching).  The
    aligned single-batch path is the special case of equal entries.
    """
    groups = []
    for (start, count, period) in layer_groups(cfg):
        n_p = count // period
        slots = []
        for s in range(period):
            kind = cfg.layer_kind(start + s)
            if kind == "ssm":
                st = S.init_ssm_state(cfg, batch)
                slots.append(jax.tree.map(
                    lambda a: jnp.zeros((n_p, *a.shape), a.dtype), st))
            elif cfg.attn_type == "mla":
                dim = cfg.kv_lora_rank + cfg.qk_rope_head_dim
                slots.append({
                    "c_q": jnp.zeros((n_p, batch, max_len, dim), jnp.int8),
                    "c_s": jnp.zeros((n_p, batch, max_len, 1), jnp.float32)})
            else:
                # lane-dense K/V rows (see repro.core.kvcache)
                kv = (n_p, batch, max_len, cfg.n_kv_heads * cfg.head_dim)
                sc = (n_p, batch, max_len, cfg.n_kv_heads, 1)
                slots.append({
                    "k_q": jnp.zeros(kv, jnp.int8), "k_s": jnp.zeros(sc, jnp.float32),
                    "v_q": jnp.zeros(kv, jnp.int8), "v_s": jnp.zeros(sc, jnp.float32)})
        groups.append(tuple(slots))
    return {"groups": tuple(groups), "pos": jnp.zeros((batch,), jnp.int32)}


def write_slot(state: dict, slot: jax.Array, one: dict) -> dict:
    """Land a single-request decode state (batch=1) into row ``slot`` of a
    pooled multi-slot state — the admission step of continuous batching.

    Every cache leaf under ``groups`` carries the slot axis at position 1
    ([n_p, B, ...]); ``pos`` is the [B] per-slot position vector.
    """
    slot = jnp.asarray(slot, jnp.int32)
    new_groups = jax.tree.map(
        lambda full, row: jax.lax.dynamic_update_slice_in_dim(
            full, row.astype(full.dtype), slot, axis=1),
        state["groups"], one["groups"])
    pos = jax.lax.dynamic_update_slice(
        jnp.asarray(state["pos"], jnp.int32),
        jnp.asarray(one["pos"], jnp.int32).reshape(1), (slot,))
    return {"groups": new_groups, "pos": pos}


def read_slot(state: dict, slot: jax.Array) -> dict:
    """Lift row ``slot`` of a pooled decode state out as a batch=1 state —
    the exact inverse of :func:`write_slot`, and the device-side half of a
    tiered-pool swap-out: the int8 payload + scales leave the pool verbatim,
    so a block that round-trips through the cold tier and lands back via
    :func:`write_slot` is byte-identical to the rows that left.
    """
    slot = jnp.asarray(slot, jnp.int32)
    groups = jax.tree.map(
        lambda full: jax.lax.dynamic_index_in_dim(full, slot, axis=1,
                                                  keepdims=True),
        state["groups"])
    pos = jax.lax.dynamic_slice(
        jnp.asarray(state["pos"], jnp.int32), (slot,), (1,))
    return {"groups": groups, "pos": pos}


def copy_slot_prefix(state: dict, src: jax.Array, dst: jax.Array,
                     n: jax.Array) -> dict:
    """Prefix-cache row gather: ``dst``'s first ``n`` sequence rows of every
    cache leaf become ``src``'s (int8 payload + scales copied verbatim, so
    the reused prefix is bit-identical to the cached one), and ``pos[dst]``
    becomes ``n`` — the slot now holds exactly the cached prefix.  Rows at
    or past ``n`` keep ``dst``'s dead in-place entries (masked, then
    overwritten by the resumed chunked prefill's finalize).

    GQA pools only: every leaf has the slot axis at 1 and the sequence
    axis at 2 (the layout :func:`init_decode_state` builds for attention
    stacks).  ``src``,
    ``dst`` and ``n`` are traced, so one compile serves every admission.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    n = jnp.asarray(n, jnp.int32)

    def one(leaf: jax.Array) -> jax.Array:
        row = jax.lax.dynamic_index_in_dim(leaf, src, axis=1, keepdims=True)
        old = jax.lax.dynamic_index_in_dim(leaf, dst, axis=1, keepdims=True)
        keep = (jnp.arange(leaf.shape[2]) < n).reshape(
            (1, 1, leaf.shape[2]) + (1,) * (leaf.ndim - 3))
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.where(keep, row, old), dst, axis=1)

    groups = jax.tree.map(one, state["groups"])
    pos = jnp.asarray(state["pos"], jnp.int32).at[dst].set(n)
    return {"groups": groups, "pos": pos}


def warm_prefill_carry(cfg: ModelConfig, state: dict, slot: jax.Array,
                       n: jax.Array, buf_len: int) -> dict:
    """Chunked-prefill carry seeded from rows ``[0:n)`` of pool ``slot`` —
    the prefix-cache warm start.  The cached int8 rows dequantize into the
    float K/V carry at the same positions, the cursor starts at ``n``, and
    chunked prefill resumes mid-prompt exactly as if the first ``n`` tokens
    had just been consumed.

    Because :func:`repro.core.quant.quantize_kv` round-trips exactly
    (dequantize -> requantize reproduces the int8 payload; the max element
    of every (token, head) row quantizes to +/-127), the finalize that
    rewrites the whole slot row at the end of the resumed prefill lands
    byte-identical int8 on the cached prefix — aliased leaves survive their
    writer's finalize untouched.

    GQA attention stacks only: the MLA pool caches the compressed latent
    (reconstructing the carry's per-head K/V needs per-layer weights) and
    SSM state cannot restart mid-prompt — the serve engine silently
    disables the prefix cache for both, mirroring ``chunk``/``spec_tree``.
    """
    if cfg.attn_type == "mla":
        raise NotImplementedError(
            "prefix-cache warm start needs per-head K/V in the pool; the "
            "MLA latent cache cannot seed the float carry without weights")
    n = jnp.asarray(n, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    groups = []
    for bufs in state["groups"]:
        slots = []
        for b in bufs:
            if "k_q" not in b:
                raise NotImplementedError(
                    "prefix-cache warm start targets GQA attention pools")
            n_p, _, S, H, _ = b["k_s"].shape
            w = min(S, buf_len)
            keep = (jnp.arange(w) < n).reshape(1, 1, w, 1, 1)

            def dequant(q, s):
                row_q = jax.lax.dynamic_index_in_dim(q, slot, 1, keepdims=True)
                row_s = jax.lax.dynamic_index_in_dim(s, slot, 1, keepdims=True)
                row = KV.split_heads(row_q, H).astype(jnp.float32) * row_s
                buf = jnp.zeros((n_p, 1, buf_len) + row.shape[3:],
                                jnp.float32)
                return buf.at[:, :, :w].set(
                    jnp.where(keep, row[:, :, :w], 0.0))

            slots.append({"k": dequant(b["k_q"], b["k_s"]),
                          "v": dequant(b["v_q"], b["v_s"])})
        groups.append(tuple(slots))
    return {"groups": tuple(groups),
            "pos": jnp.broadcast_to(n, (1,)).astype(jnp.int32)}


_KV_LEAVES = ("k_q", "k_s", "v_q", "v_s")


def _kv_attend(body, cache: dict, rows, q, pos_b, rt: Runtime, *extra):
    """Run ``body(cache, rows, q, pos_b, *extra) -> (cache, o)`` — write
    the tokens' int8 K/V rows and scales into ``cache`` and attend ``q``
    ([B, T, H, D]) over them — on the GQA leaves of ``cache``: the carried
    pool ([n_p, B, S, ...]) or one layer of it ([B, S, ...]).

    On a mesh each device writes and attends its own slots and KV-head
    groups inside ``shard_map``: a per-slot update at a constant slot index
    on the data-sharded slot axis would otherwise make the partitioner
    all-gather the whole pool per slot, and a contraction over the merged
    lane axis would make it all-gather the layer's K/V.  Each ``extra``
    operand is a replicated scalar or a per-slot [B, ...] array."""
    cache = {n: cache[n] for n in _KV_LEAVES}
    if rt.mesh is None:
        return body(cache, rows, q, pos_b, *extra)
    from repro.dist import sharding as SH
    lead = cache["k_s"].ndim - 4                  # 1 on the stacked pool
    B, G = cache["k_s"].shape[lead], cache["k_s"].shape[lead + 2]
    pool = SH.kv_pool_spec(B, G, rt.mesh)
    b, m = pool["k_s"][1], pool["k_s"][3]
    at = {n: P(*s[1 - lead:]) for n, s in pool.items()}
    per_slot = lambda a: P(b, *(None,) * (jnp.ndim(a) - 1)) if jnp.ndim(a) else P()
    return jax.shard_map(
        body, mesh=rt.mesh,
        in_specs=(at, tuple(P(*pool[n][1:]) for n in _KV_LEAVES),
                  P(b, None, m, None), P(b)) + tuple(map(per_slot, extra)),
        out_specs=(at, P(b, None, m, None)))(
            cache, tuple(rows), q, pos_b, *extra)


def apply_layer_decode(p: Params, cfg: ModelConfig, slot: int, x, pos,
                       caches, idx, rt: Runtime, counts: dict | None = None):
    """One decode layer against ``caches``, the layer stack's carried cache
    (leaves [n_p, B, S, ...]), at layer ``idx``.  Returns (x, caches,
    counts), the MoE counters added to ``counts`` unless it is None.

    A GQA layer appends its int8 K/V rows and scales straight into the
    carried pool (:func:`KV.append_rows`) and attends over layer ``idx``
    as it lies there (:func:`A.decode_attention_int8`): the pool is never
    sliced out and written back whole.
    SSM and MLA layers update their layer's slice and write it back."""
    kind = cfg.layer_kind(slot)
    dmvm_dt = rt.dmvm_dtype or jnp.float32
    h = L.apply_norm(p["ln1"], x, cfg.norm_eps)
    if kind != "ssm" and cfg.attn_type != "mla":
        pos_b = KV.slot_positions(pos, x.shape[0])
        q, rows = A.gqa_decode_rows(p["attn"], cfg, h, pos_b[:, None],
                                    rt.backend)

        def append_attend(c, r, qq, p, i):
            c = {n: KV.append_rows(c[n], i, rr, p)
                 for n, rr in zip(_KV_LEAVES, r)}
            layer = (jax.lax.dynamic_index_in_dim(c[n], i, 0, keepdims=False)
                     for n in _KV_LEAVES)
            return c, A.decode_attention_int8(qq, *layer, p + 1, rt.backend,
                                              dmvm_dt)
        caches, o = _kv_attend(append_attend, caches, rows, q, pos_b, rt, idx)
        mix = A.gqa_decode_out(p["attn"], o, rt.backend)
    else:
        cache = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
            caches)
        if kind == "ssm":
            mix, new = S.ssm_decode(p["ssm"], cfg, h, cache, rt.backend)
        else:
            mix, (c_q, c_s) = A.mla_decode(p["attn"], cfg, h, pos,
                                           cache["c_q"], cache["c_s"],
                                           rt.backend, dmvm_dt)
            new = {"c_q": c_q, "c_s": c_s}
        caches = jax.tree.map(
            lambda full, n: jax.lax.dynamic_update_slice_in_dim(
                full, n[None].astype(full.dtype), idx, 0), caches, new)
    x, counts = _ffn(p, x + mix, cfg, rt, counts)
    return x, caches, counts


def decode_step(p: Params, cfg: ModelConfig, state: dict, token: jax.Array,
                rt: Runtime, counts: bool = False):
    """token: [B] (or [B, d] embedding) -> (logits [B, V], new state).
    ``state["pos"]`` is [B]: slots decode at heterogeneous positions.
    With ``counts`` a third output holds the step's MoE routing counters
    summed over its layers (:func:`zero_counts`)."""
    pos = jnp.broadcast_to(jnp.asarray(state["pos"], jnp.int32),
                           (token.shape[0],))
    if cfg.input_mode == "embeddings" and token.ndim == 2:
        x = token[:, None, :]
    else:
        x = p["embed"]["w"][token][:, None]
    if not cfg.rope_theta:
        x = x + _sinusoid_at(pos, cfg.d_model).astype(x.dtype)[:, None]
    new_groups = []
    cnt = zero_counts() if counts else None
    for (start, count, period), slots, caches in zip(
            layer_groups(cfg), p["groups"], state["groups"]):
        n_p = jax.tree.leaves(slots[0])[0].shape[0]

        def body(carry, xs):
            xx, full_caches, c = carry
            slot_trees, idx = xs
            new_full = []
            for s in range(period):
                xx, full, c = apply_layer_decode(slot_trees[s], cfg, start + s,
                                                 xx, pos, full_caches[s], idx,
                                                 rt, c)
                new_full.append(full)
            return (xx, tuple(new_full), c), None

        (x, new_caches, cnt), _ = jax.lax.scan(
            body, (x, caches, cnt), (slots, jnp.arange(n_p)))
        new_groups.append(new_caches)
    x = L.apply_norm(p["ln_f"], x, cfg.norm_eps)
    logits = _lm_head(p, cfg, x[:, 0], rt)
    state = {"groups": tuple(new_groups), "pos": pos + 1}
    return (logits, state, cnt) if counts else (logits, state)


def multi_decode_step(p: Params, cfg: ModelConfig, state: dict,
                      token: jax.Array, m: int, rt: Runtime,
                      counts: bool = False):
    """Fused multi-step greedy decode: run ``m`` :func:`decode_step`
    iterations in one jitted ``lax.scan``, feeding each step's argmax back
    on device — the device-resident decode loop.  ``token`` is the [B]
    vector of last committed tokens per slot.

    Returns ``(tokens [B, m] int32, state advanced by m)``.  Each scan
    iteration is exactly one :func:`decode_step` (same K/V append at the
    per-slot cursor, same int8 dMVM attention), and
    ``jnp.argmax`` breaks ties by lowest token id like the host sampler, so
    the emitted block is token-identical to ``m`` host-driven single steps
    — only the per-token host round-trip disappears.  A caller that stops a
    slot mid-block (EOS / budget) commits the accepted prefix by rewinding
    that slot's cursor (:func:`rewind_pos`); the overshoot rows die in
    place under the SLC write-in-place discipline, exactly like a rejected
    speculative suffix.  The pool needs ``m - 1`` rows of headroom past
    ``max_len`` so overshoot appends never clamp-wrap onto live rows (the
    serve engine sizes its pool accordingly).

    Works for any stack :func:`decode_step` accepts (the scan body is the
    single step), but engines must not fuse SSM/hybrid stacks: their
    recurrent state cannot rewind, so mid-block stops could not roll back.
    With ``counts`` a third output sums the ``m`` steps' MoE counters.
    """
    def body(carry, _):
        tok, st, c = carry
        if counts:
            logits, st, c1 = decode_step(p, cfg, st, tok, rt, counts=True)
            c = jax.tree.map(jnp.add, c, c1)
        else:
            logits, st = decode_step(p, cfg, st, tok, rt)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return (nxt, st, c), nxt

    (_, new_state, cnt), toks = jax.lax.scan(
        body, (jnp.asarray(token, jnp.int32), state,
               zero_counts() if counts else None), None, length=m)
    if counts:
        return toks.T, new_state, cnt
    return toks.T, new_state                              # [B, m]


# ---------------------------------------------------------------------------
# speculative decode: batched multi-token verify + cursor rollback + MTP draft
# ---------------------------------------------------------------------------
def apply_layer_verify(p: Params, cfg: ModelConfig, slot: int, x, pos, cache,
                       rt: Runtime, depth, anc):
    """One layer of the speculative verify pass: like
    :func:`apply_layer_decode` but over ``x`` [B, T, d], the T nodes of a
    per-slot draft tree (``depth``/``anc`` both [B, T] int32; T = root +
    drafted nodes).  Node i's K/V row lands at cache offset ``pos + i`` in
    one multi-token :func:`KV.batched_update` (the SLC append chunked
    prefill makes too), RoPE rotates it at its tree depth
    (``pos + depth[b, i]``), and all T queries are scored over the layer in
    one ancestor-masked pass (:func:`A.verify_attention_int8`).  Each
    node's K row and scores match what sequential decode of its root-path
    would produce — chain-prefix nodes bit-exactly (a chain, ``depth[b, i]
    = i``, is T sequential decode steps), which is what makes greedy
    speculative decode token-identical to the plain engine; past a skipped
    sibling, up to float reduction order (see
    :func:`attention.verify_attention_int8`)."""
    dmvm_dt = rt.dmvm_dtype or jnp.float32
    h = L.apply_norm(p["ln1"], x, cfg.norm_eps)
    if cfg.attn_type == "mla":
        mix, (c_q, c_s) = A.mla_verify(p["attn"], cfg, h, pos, cache["c_q"],
                                       cache["c_s"], depth, anc, rt.backend,
                                       dmvm_dt)
        new_cache = {"c_q": c_q, "c_s": c_s}
    else:
        pos_b = KV.slot_positions(pos, x.shape[0])
        q, rows = A.gqa_decode_rows(p["attn"], cfg, h, pos_b[:, None] + depth,
                                    rt.backend)

        def update_attend(c, r, qq, p, a):
            c = {n: KV.batched_update(c[n], rr, p)
                 for n, rr in zip(_KV_LEAVES, r)}
            return c, A.verify_attention_int8(
                qq, *(c[n] for n in _KV_LEAVES), p, a, rt.backend, dmvm_dt)
        new_cache, o = _kv_attend(update_attend, cache, rows, q, pos_b, rt,
                                  anc)
        mix = A.gqa_decode_out(p["attn"], o, rt.backend)
    x, _ = _ffn(p, x + mix, cfg, rt, None)
    return x, new_cache


def verify_step(p: Params, cfg: ModelConfig, state: dict, tokens: jax.Array,
                rt: Runtime, depth: jax.Array, anc: jax.Array,
                ) -> tuple[jax.Array, jax.Array, dict]:
    """Speculative-decode verify: feed ``tokens`` [B, T] — per slot a draft
    *tree* in topological order (node 0 = root = last committed token) —
    at each slot's cursor in one batched pass.  ``depth``/``anc`` ([B, T]
    int32) give each node's tree depth and ancestor-or-self bitmask (bit j
    set iff node j is an ancestor-or-self of node i); a chain of T-1
    drafts is ``depth = [0, 1, ...]``, ``anc = [1, 3, 7, ...]``.

    Returns ``(logits [B, T, V], hidden [B, T, d], new state)`` — row ``i``
    of ``logits`` is the model's next-token distribution after node i's
    root-path, what sequential :func:`decode_step` calls along that path
    would produce: bit-exactly for chain-prefix nodes, and up to float
    reduction order (~1 ulp) past a skipped sibling
    (:func:`repro.models.attention.verify_attention_int8`), so greedy
    acceptance is lossless.  ``hidden`` is the post-``ln_f`` hidden state
    per node (the MTP drafter's recursion carry).  The returned state has
    ``pos + T`` and all T K/V rows appended at ``pos .. pos + T - 1``; the
    caller walks the tree host-side and commits the longest accepted
    root-path with :func:`tree_commit` (or, when that path is the chain
    prefix, by *rewinding* the cursor, :func:`rewind_pos`) — rejected rows
    stay in the SLC region as dead entries that the position mask hides
    and the next in-place append overwrites (no erase cycle).

    Attention-family stacks only: an SSM layer's recurrent state cannot be
    rewound without checkpointing, so SSM/hybrid engines keep the plain
    one-token decode loop.
    """
    if any(cfg.layer_kind(i) == "ssm" for i in range(cfg.n_layers)):
        raise NotImplementedError(
            "speculative verify needs a rewindable cache; SSM/hybrid stacks "
            "keep the one-token decode path (see serve engine)")
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(state["pos"], jnp.int32), (B,))
    x = p["embed"]["w"][tokens]
    if not cfg.rope_theta:
        x = x + _sinusoid_at(pos[:, None] + depth,
                             cfg.d_model).astype(x.dtype)
    new_groups = []
    for (start, count, period), slots, caches in zip(
            layer_groups(cfg), p["groups"], state["groups"]):
        n_p = jax.tree.leaves(slots[0])[0].shape[0]

        def body(carry, xs):
            xx, full_caches = carry
            slot_trees, idx = xs
            new_full = []
            for s in range(period):
                cache_s = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0,
                                                           keepdims=False),
                    full_caches[s])
                xx, nc = apply_layer_verify(slot_trees[s], cfg, start + s, xx,
                                            pos, cache_s, rt, depth, anc)
                new_full.append(jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_slice_in_dim(
                        full, new[None].astype(full.dtype), idx, 0),
                    full_caches[s], nc))
            return (xx, tuple(new_full)), None

        (x, new_caches), _ = jax.lax.scan(
            body, (x, caches), (slots, jnp.arange(n_p)))
        new_groups.append(new_caches)
    x = L.apply_norm(p["ln_f"], x, cfg.norm_eps)
    logits = _lm_head(p, cfg, x, rt)
    return logits, x, {"groups": tuple(new_groups), "pos": pos + T}


def rewind_pos(state: dict, pos) -> dict:
    """Speculative-decode rollback: commit each slot's accepted prefix by
    rewinding its cursor to ``pos`` ([B] int32).  SLC writes are in place,
    so the rejected suffix needs no erase — its rows are dead (masked by
    ``pos``) until the next append overwrites them."""
    return {"groups": state["groups"], "pos": jnp.asarray(pos, jnp.int32)}


def tree_commit(state: dict, base, sel, keep, pos) -> dict:
    """Tree-spec commit: compact each slot's accepted root-path rows into
    contiguous committed rows, then rewind the cursor — the tree sibling of
    :func:`rewind_pos`.

    ``base``/``keep``: [B] int32 (pre-window cursor, accepted path length);
    ``sel``: [B, W] in-window node indices of the path in order.  Node
    ``sel[b, w]``'s row (at ``base + sel[b, w]``, RoPE'd at its tree depth
    ``base + 1 + w``) moves to row ``base + 1 + w`` — after the gather every
    committed row sits at the position it was encoded at, the state
    sequential decode would have built (the gather copies node K/V rows
    verbatim; chain-prefix nodes are bit-identical to sequential appends,
    nodes past a skipped sibling match up to float reduction order — see
    :func:`verify_step`).  ``pos`` is the
    [B] post-commit cursor (= base + 1 + keep for slots that ran a window,
    unchanged elsewhere); rejected branches die in place per the SLC
    write-in-place discipline."""
    groups = jax.tree.map(lambda leaf: KV.path_gather(leaf, base, sel, keep),
                          state["groups"])
    return {"groups": groups, "pos": jnp.asarray(pos, jnp.int32)}


def _mtp_cell(p: Params, cfg: ModelConfig, h, tok, pos_i, rt: Runtime):
    """One MTP-head step: project ``[h; embed(tok)]`` through
    ``mtp_proj``/``mtp_layer`` at position ``pos_i`` -> (logits, new h)."""
    emb = p["embed"]["w"][tok].astype(h.dtype)                  # [B, d]
    hcat = jnp.concatenate([h, emb], axis=-1)
    hm = L.apply_linear(L._lin(p["mtp_proj"], "w"), hcat, rt.backend)
    hm3, _ = apply_layer_train(p["mtp_layer"], cfg, cfg.n_layers - 1,
                               hm[:, None, :], pos_i[:, None], rt)
    return _lm_head(p, cfg, hm3[:, 0], rt), hm3[:, 0]


def mtp_chain_lengths(n: int, branch: int) -> list[int]:
    """Per-chain node budgets for the MTP draft-tree beam: ``n`` draft
    nodes split across ``min(branch, n)`` root-child chains, earlier
    chains longer.  Shared by :func:`mtp_draft_tree` and the host-side
    parent-pointer construction so both agree on the static topology."""
    b = max(1, min(branch, n))
    return [n // b + (1 if j < n % b else 0) for j in range(b)]


def mtp_draft_tree(p: Params, cfg: ModelConfig, hidden: jax.Array,
                   token: jax.Array, pos: jax.Array, n: int, branch: int,
                   rt: Runtime) -> jax.Array:
    """Beam the MTP head into a static draft tree: the top-``branch``
    tokens of the head's first distribution each root a chain extended
    greedily (each chain feeds its own token back through the recursive
    head), with node budgets from :func:`mtp_chain_lengths`.

    Returns tokens [B, n] in chain-major node order — chain j's nodes are
    consecutive, first node a child of the root.  At ``branch=1`` this is
    one greedy chain.  The topology is static per (n, branch), so the
    engine derives parent pointers host-side.

    ``hidden`` [B, d] is the post-``ln_f`` hidden at the last committed
    position (from :func:`verify_step`; zeros right after prefill — the
    head free-runs from the embedding alone there).  Each step is
    single-position (the MTP layer's attention sees only its own token, no
    KV cache), so the draft is cheap but approximate — the verify step
    makes any draft quality lossless; it only costs acceptance rate."""
    if not cfg.mtp:
        raise ValueError(f"{cfg.name} has no MTP head (cfg.mtp is False)")
    lens = mtp_chain_lengths(n, branch)
    h = hidden.astype(jnp.float32)
    tok = jnp.asarray(token, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    logits0, h1 = _mtp_cell(p, cfg, h, tok, pos, rt)
    _, top = jax.lax.top_k(logits0, len(lens))                  # [B, b]
    drafts = []
    for j, clen in enumerate(lens):
        hj = h1
        tj = top[:, j].astype(jnp.int32)
        drafts.append(tj)
        for s in range(1, clen):
            lg, hj = _mtp_cell(p, cfg, hj, tj, pos + s, rt)
            tj = jnp.argmax(lg, -1).astype(jnp.int32)
            drafts.append(tj)
    return jnp.stack(drafts, axis=1)                            # [B, n]


def _sinusoid_at(pos: jax.Array, d: int) -> jax.Array:
    """Sinusoidal embedding at ``pos`` (scalar -> [d]; [B] -> [B, d]) with no
    table materialisation — each slot sits at its own position."""
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    ang = jnp.asarray(pos).astype(jnp.float32)[..., None] * div
    pe = jnp.zeros((*ang.shape[:-1], d), jnp.float32)
    return pe.at[..., 0::2].set(jnp.sin(ang)).at[..., 1::2].set(jnp.cos(ang))


# ---------------------------------------------------------------------------
# prefill: run the train forward but also build the decode cache
# ---------------------------------------------------------------------------
def prefill(p: Params, cfg: ModelConfig, inputs: jax.Array, max_len: int,
            rt: Runtime, lengths: jax.Array | None = None,
            counts: bool = False):
    """Process a prompt of length T; return (last-token logits, decode state).

    The prefill pass is the "GPU stage" of the paper's pipeline: full-width
    bf16 compute, after which K/V are quantized into the int8 SLC cache.

    ``lengths`` ([B] int32, optional) admits a *ragged* right-padded batch:
    attention masks each row's keys to its own prefix, logits are gathered at
    each row's last real token, and the returned state carries per-slot
    positions.  Exact for attention layers (causal masking isolates the
    padded tail); SSM/hybrid stacks scan the padding through their recurrent
    state, so ragged prefill for those families should go through per-request
    prefill instead (the serve engine does).

    With ``counts`` a third output holds the MoE routing counters summed
    over the layers (:func:`zero_counts`), of the rows within ``lengths``.
    """
    x = _embed(p, cfg, inputs)
    B, T = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if lengths is not None:
        lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    state = init_decode_state(cfg, B, max_len)
    new_groups = []
    cnt = zero_counts() if counts else None
    real = None if lengths is None else positions < lengths[:, None]
    for (start, count, period), slots, caches in zip(
            layer_groups(cfg), p["groups"], state["groups"]):
        def body(carry, xs):
            xx, cn = carry
            slot_trees, slot_caches = xs
            new_c = []
            for s in range(period):
                slot = start + s
                kind = cfg.layer_kind(slot)
                pp = slot_trees[s]
                h = L.apply_norm(pp["ln1"], xx, cfg.norm_eps)
                if kind == "ssm":
                    mix, nc = S.ssm_forward(pp["ssm"], cfg, h,
                                            backend=rt.backend,
                                            return_state=True)
                elif cfg.attn_type == "mla":
                    # rt threads through so prefill RoPE takes the
                    # partition-safe form under a mesh (rotate-half's
                    # split+concat triggers SPMD full rematerialisation
                    # inside this layer scan)
                    mix, latent = A.mla_forward(pp["attn"], cfg, h, positions,
                                                rt.backend, lengths=lengths,
                                                rt=rt)
                    amax = jnp.max(jnp.abs(latent), -1, keepdims=True)
                    sc = jnp.maximum(amax, 1e-8) / 127.0
                    lq = jnp.clip(jnp.round(latent / sc), -127, 127).astype(jnp.int8)
                    c = slot_caches[s]
                    nc = {"c_q": jax.lax.dynamic_update_slice(
                              c["c_q"], lq, (0, 0, 0)),
                          "c_s": jax.lax.dynamic_update_slice(
                              c["c_s"], sc.astype(jnp.float32), (0, 0, 0))}
                else:
                    mix, (k, v) = A.gqa_forward(pp["attn"], cfg, h, positions,
                                                rt.backend, lengths=lengths,
                                                rt=rt)
                    # land k/v on the cache's sharding *before* quantizing so
                    # the quantize+update pipeline doesn't bounce layouts
                    # (SPMD otherwise falls back to full rematerialisation)
                    if rt.mesh is not None:
                        from jax.sharding import NamedSharding
                        kv_spec = P(rt.data_axes, rt.model_axis, None, None)
                        k = jax.lax.with_sharding_constraint(
                            k, NamedSharding(rt.mesh, kv_spec))
                        v = jax.lax.with_sharding_constraint(
                            v, NamedSharding(rt.mesh, kv_spec))
                    c = slot_caches[s]
                    nc = {n: jax.lax.dynamic_update_slice(c[n], r,
                                                          (0,) * c[n].ndim)
                          for n, r in zip(_KV_LEAVES, (*KV.quantize_rows(k),
                                                       *KV.quantize_rows(v)))}
                xx, cn = _ffn(pp, xx + mix, cfg, rt, cn, real)
                new_c.append(nc)
            return (xx, cn), tuple(new_c)
        (x, cnt), new_caches = jax.lax.scan(body, (x, cnt), (slots, caches))
        new_groups.append(new_caches)
    x = L.apply_norm(p["ln_f"], x, cfg.norm_eps)
    if lengths is None:
        last = x[:, -1]
        pos = jnp.full((B,), T, jnp.int32)
    else:
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        pos = lengths
    logits = _lm_head(p, cfg, last, rt)
    state = {"groups": tuple(new_groups), "pos": pos}
    return (logits, state, cnt) if counts else (logits, state)


# ---------------------------------------------------------------------------
# chunked prefill: the prompt is consumed [1, C] tokens at a time so decode
# iterations never stall behind a full-prompt prefill (vLLM-style chunked
# prefill mapped onto the paper's SLC-slot residency)
# ---------------------------------------------------------------------------
def init_prefill_carry(cfg: ModelConfig, buf_len: int) -> dict:
    """Float K/V carry for one in-flight chunked prefill (B=1).

    The carry is the full-precision working set of the "GPU stage": each
    attention layer keeps [n_p, 1, buf_len, H, D] float K/V so later chunks
    attend the earlier prefix at prefill precision (what makes chunked
    prefill token-identical to one-shot).  MLA additionally carries the
    compressed latent, which is what finalization quantizes into the SLC
    cache.  ``buf_len`` should be ``max_len + chunk`` so a ragged final
    chunk's padded tail never clamp-wraps into valid rows.

    SSM/hybrid stacks keep the exact-length prefill path (their recurrent
    state would integrate chunk-boundary error) — requesting a carry for one
    raises.
    """
    groups = []
    for (start, count, period) in layer_groups(cfg):
        n_p = count // period
        slots = []
        for s in range(period):
            if cfg.layer_kind(start + s) == "ssm":
                raise NotImplementedError(
                    "chunked prefill carries attention K/V only; SSM/hybrid "
                    "stacks prefill at exact length (see serve engine)")
            if cfg.attn_type == "mla":
                slots.append({
                    "k": jnp.zeros((n_p, 1, buf_len, cfg.n_heads,
                                    cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
                                   jnp.float32),
                    "v": jnp.zeros((n_p, 1, buf_len, cfg.n_heads,
                                    cfg.v_head_dim), jnp.float32),
                    "lat_c": jnp.zeros((n_p, 1, buf_len, cfg.kv_lora_rank),
                                       jnp.float32),
                    "lat_r": jnp.zeros((n_p, 1, buf_len, cfg.qk_rope_head_dim),
                                       jnp.float32)})
            else:
                kv = (n_p, 1, buf_len, cfg.n_kv_heads, cfg.head_dim)
                slots.append({"k": jnp.zeros(kv, jnp.float32),
                              "v": jnp.zeros(kv, jnp.float32)})
        groups.append(tuple(slots))
    return {"groups": tuple(groups), "pos": jnp.zeros((1,), jnp.int32)}


def prefill_chunk(p: Params, cfg: ModelConfig, carry: dict, tokens: jax.Array,
                  n_real: jax.Array, rt: Runtime) -> tuple[jax.Array, dict]:
    """Consume one ``[1, C]`` token chunk at the carry's cursor.

    ``n_real`` (traced scalar) is the number of real tokens in the chunk —
    the final chunk of a prompt is right-padded to C, and a chunk may be cut
    short by the engine's per-iteration token budget.  Returns (logits of
    the chunk's last real token [1, V], updated carry).  The cursor
    (``carry["pos"]``) advances by ``n_real``, so one compiled step serves
    every offset and every ragged tail.
    """
    C = tokens.shape[1]
    pos0 = jnp.asarray(carry["pos"], jnp.int32)[0]
    n_real = jnp.asarray(n_real, jnp.int32)
    x = _embed(p, cfg, tokens, pos_offset=pos0)
    positions = jnp.broadcast_to(pos0 + jnp.arange(C), (1, C))
    kv_lengths = jnp.broadcast_to(pos0 + n_real, (1,))
    new_groups = []
    for (start, count, period), slots, bufs in zip(
            layer_groups(cfg), p["groups"], carry["groups"]):
        def body(xx, xs):
            slot_trees, slot_bufs = xs
            new_b = []
            for s in range(period):
                pp = slot_trees[s]
                h = L.apply_norm(pp["ln1"], xx, cfg.norm_eps)
                if cfg.attn_type == "mla":
                    mix, nb = A.mla_chunk(pp["attn"], cfg, h, positions,
                                          slot_bufs[s], pos0, kv_lengths, rt)
                else:
                    mix, nb = A.gqa_chunk(pp["attn"], cfg, h, positions,
                                          slot_bufs[s], pos0, kv_lengths, rt)
                xx, _ = _ffn(pp, xx + mix, cfg, rt, None)
                new_b.append(nb)
            return xx, tuple(new_b)
        x, nb = jax.lax.scan(body, x, (slots, bufs))
        new_groups.append(nb)
    x = L.apply_norm(p["ln_f"], x, cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.reshape(n_real - 1, (1, 1, 1)).astype(jnp.int32), axis=1)[:, 0]
    logits = _lm_head(p, cfg, last, rt)
    return logits, {"groups": tuple(new_groups),
                    "pos": jnp.asarray(carry["pos"], jnp.int32) + n_real}


def finalize_prefill_carry(cfg: ModelConfig, carry: dict, max_len: int) -> dict:
    """Quantize a completed chunked-prefill carry into a B=1 decode state —
    the prefill->decode KV handoff (float "GPU stage" K/V landing as int8
    in the SLC region).  Per-(token, head) quantization means the int8 rows
    are the same the one-shot prefill would have written.  The result plugs
    straight into :func:`write_slot`."""
    groups = []
    for bufs in carry["groups"]:
        slots = []
        for b in bufs:
            if "lat_c" in b:                     # MLA latent cache
                lat = jnp.concatenate([b["lat_c"], b["lat_r"]],
                                      axis=-1)[:, :, :max_len]
                amax = jnp.max(jnp.abs(lat), -1, keepdims=True)
                sc = jnp.maximum(amax, 1e-8) / 127.0
                lq = jnp.clip(jnp.round(lat / sc), -127, 127).astype(jnp.int8)
                slots.append({"c_q": lq, "c_s": sc.astype(jnp.float32)})
            else:
                slots.append(dict(zip(_KV_LEAVES, (
                    *KV.quantize_rows(b["k"][:, :, :max_len]),
                    *KV.quantize_rows(b["v"][:, :, :max_len])))))
        groups.append(tuple(slots))
    return {"groups": tuple(groups),
            "pos": jnp.asarray(carry["pos"], jnp.int32)}


# ---------------------------------------------------------------------------
# loss (chunked over sequence to bound logits memory)
# ---------------------------------------------------------------------------
def lm_loss(p: Params, cfg: ModelConfig, inputs, labels, rt: Runtime,
            chunk: int = 512) -> jax.Array:
    h, aux = forward_train(p, cfg, inputs, rt)
    B, T = h.shape[:2]
    n_chunks = max(1, T // chunk)
    if T % n_chunks:
        n_chunks = 1
    hc = h.reshape(B, n_chunks, T // n_chunks, -1)
    lc = labels.reshape(B, n_chunks, T // n_chunks)

    def chunk_loss(carry, xs):
        hh, ll = xs
        logits = _lm_head(p, cfg, hh, rt).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32),
                            (hc.transpose(1, 0, 2, 3), lc.transpose(1, 0, 2)))
    loss = total / (B * T)
    if cfg.mtp:
        loss = loss + 0.3 * _mtp_loss(p, cfg, h, inputs, labels, rt, chunk)
    return loss + 0.01 * aux


def _mtp_loss(p, cfg, h, inputs, labels, rt, chunk):
    """DeepSeek-V3 multi-token prediction (depth 1): predict t+2."""
    emb_next = _embed(p, cfg, inputs)[:, 1:]
    hcat = jnp.concatenate([h[:, :-1], emb_next], axis=-1)
    hm = L.apply_linear(L._lin(p["mtp_proj"], "w"), hcat, rt.backend)
    B, Tm = hm.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(Tm), (B, Tm))
    hm, _ = apply_layer_train(p["mtp_layer"], cfg, cfg.n_layers - 1, hm,
                              positions, rt)
    # hm[:, t] (from h_t and emb of token t+1) predicts labels[t+1] = token t+2
    logits = _lm_head(p, cfg, hm, rt).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, 1:][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
