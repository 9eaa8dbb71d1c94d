"""The decode layer scan appends each layer's int8 K/V rows in place into the
carried pool and attends over the layer read back from it.

Covers:

* equivalence with the slice formulation (slice a layer's cache out of the
  carried pool, append with ``KV.batched_update``, attend over the copy,
  write the slice back), written here as the reference: the pool's int8
  bytes and f32 scales are equal exactly, logits to float tolerance, for
  an MHA stack whose head_dim is not a multiple of 128 and for a GQA
  stack, at one and four slots, with a slot at ``max_len - 1``;
* structure: no ``dynamic_update_slice`` or ``scatter`` in the layer scan
  writes an update as long as the pool (the whole-slice write-back);
* MLA and SSM stacks keep the slice path and still match the reference.
"""
import dataclasses

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.models.transformer import Runtime

jax.config.update("jax_platform_name", "cpu")

MAX_LEN = 40          # no other axis of the tiny stacks is 40 long


def _reference_decode_step(p, cfg, state, token, rt):
    """``decode_step`` in the slice formulation: each layer's cache is
    sliced out of the carried pool, updated (GQA through
    :func:`attention.gqa_decode`, i.e. ``KV.batched_update``), attended
    over, and written back whole."""
    pos = jnp.broadcast_to(jnp.asarray(state["pos"], jnp.int32),
                           (token.shape[0],))
    x = p["embed"]["w"][token][:, None]
    new_groups = []
    for (start, count, period), slots, caches in zip(
            T.layer_groups(cfg), p["groups"], state["groups"]):
        n_p = jax.tree.leaves(slots[0])[0].shape[0]

        def body(carry, xs):
            xx, full_caches = carry
            slot_trees, idx = xs
            new_full = []
            for s in range(period):
                lp = slot_trees[s]
                cache = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, idx, 0, keepdims=False), full_caches[s])
                h = L.apply_norm(lp["ln1"], xx)
                if cfg.layer_kind(start + s) == "ssm":
                    mix, new = SSM.ssm_decode(lp["ssm"], cfg, h, cache,
                                              rt.backend)
                elif cfg.attn_type == "mla":
                    mix, (c_q, c_s) = A.mla_decode(
                        lp["attn"], cfg, h, pos, cache["c_q"], cache["c_s"],
                        rt.backend)
                    new = {"c_q": c_q, "c_s": c_s}
                else:
                    mix, kv = A.gqa_decode(
                        lp["attn"], cfg, h, pos, cache["k_q"], cache["k_s"],
                        cache["v_q"], cache["v_s"], rt.backend)
                    new = dict(zip(("k_q", "k_s", "v_q", "v_s"), kv))
                xx = xx + mix
                if "moe" in lp:
                    mo, _ = T._moe_block(lp["moe"],
                                         L.apply_norm(lp["ln2"], xx), cfg, rt)
                    xx = xx + mo
                elif "mlp" in lp:
                    xx = xx + L.apply_mlp(lp["mlp"],
                                          L.apply_norm(lp["ln2"], xx),
                                          cfg.mlp_type, rt.backend)
                new_full.append(jax.tree.map(
                    lambda full, n: jax.lax.dynamic_update_slice_in_dim(
                        full, n[None].astype(full.dtype), idx, 0),
                    full_caches[s], new))
            return (xx, tuple(new_full)), None

        (x, new_caches), _ = jax.lax.scan(
            body, (x, caches), (slots, jnp.arange(n_p)))
        new_groups.append(new_caches)
    x = L.apply_norm(p["ln_f"], x)
    logits = T._lm_head(p, cfg, x[:, 0], rt)
    return logits, {"groups": tuple(new_groups), "pos": pos + 1}


def _cfg(name):
    cfg = ARCHS[name].reduced()
    if name == "phi3-mini-3.8b":
        # MHA (32 KV heads at full width) whose head_dim, like the full
        # model's 96, is not a multiple of 128
        cfg = dataclasses.replace(cfg, head_dim=24)
    return cfg


def _filled_state(cfg, B, pos, seed=0):
    """A decode state whose pool holds random int8 rows, positive scales
    and random SSM state, with the slots at ``pos``."""
    state = M.init_decode_state(cfg, B, MAX_LEN)
    leaves, tree = jax.tree.flatten(state["groups"])
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    filled = []
    for k, a in zip(keys, leaves):
        if a.dtype == jnp.int8:
            filled.append(jax.random.randint(k, a.shape, -127, 128, jnp.int32)
                          .astype(jnp.int8))
        else:
            filled.append(jax.random.uniform(k, a.shape, a.dtype, 0.01, 0.05))
    return {"groups": jax.tree.unflatten(tree, filled),
            "pos": jnp.asarray(pos, jnp.int32)}


def _assert_states_equal(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


POSITIONS = {1: [MAX_LEN - 1], 4: [0, 17, MAX_LEN - 1, 5]}


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "llama3-8b"],
                         ids=["mha-hd24", "gqa"])
def test_decode_step_matches_slice_reference(name, B):
    cfg = _cfg(name)
    if name == "phi3-mini-3.8b":
        assert cfg.n_kv_heads == cfg.n_heads and cfg.head_dim % 128
    else:
        assert cfg.n_kv_heads < cfg.n_heads
    params = M.init_params(jax.random.key(1), cfg)
    rt = Runtime()
    state = _filled_state(cfg, B, POSITIONS[B])
    tok = jnp.arange(3, 3 + B, dtype=jnp.int32)
    step = jax.jit(lambda s, t: M.decode_step(params, cfg, s, t, rt))
    ref = jax.jit(lambda s, t: _reference_decode_step(params, cfg, s, t, rt))
    lg, st = step(state, tok)
    lg_ref, st_ref = ref(state, tok)
    _assert_states_equal(st, st_ref)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                               rtol=1e-5, atol=1e-5)
    # the step wrote each slot's row (the pool changed only there)
    k_old = np.asarray(state["groups"][0][0]["k_q"])
    k_new = np.asarray(st["groups"][0][0]["k_q"])
    changed = np.argwhere((k_old != k_new).any(axis=(0, 3, 4)))
    assert {tuple(c) for c in changed} == {
        (b, p) for b, p in enumerate(POSITIONS[B])}


def _scan_body_updates(jaxpr):
    """Update operands of every dynamic_update_slice / scatter inside the
    bodies of ``jaxpr``'s scans (nested calls included)."""
    def subjaxprs(eqn):
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(j, jex.core.ClosedJaxpr):
                    yield j.jaxpr
                elif isinstance(j, jex.core.Jaxpr):
                    yield j

    def walk(jx, in_scan):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if in_scan and name == "dynamic_update_slice":
                yield eqn.invars[1].aval.shape
            elif in_scan and name.startswith("scatter"):
                yield eqn.invars[2].aval.shape
            for sub in subjaxprs(eqn):
                yield from walk(sub, in_scan or name == "scan")

    return list(walk(jaxpr, False))


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "llama3-8b"],
                         ids=["mha-hd24", "gqa"])
def test_layer_scan_writes_no_whole_slice(name):
    cfg = _cfg(name)
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, 4, MAX_LEN))
    jaxpr = jax.make_jaxpr(
        lambda p, s, t: M.decode_step(p, cfg, s, t, Runtime()))(
            params, state, jax.ShapeDtypeStruct((4,), jnp.int32))
    updates = _scan_body_updates(jaxpr.jaxpr)
    assert updates                       # the appends are in the scan
    assert all(MAX_LEN not in shape for shape in updates), updates


@pytest.mark.parametrize("name", ["deepseek-v3-671b", "mamba2-2.7b",
                                  "jamba-1.5-large-398b"],
                         ids=["mla", "ssm", "hybrid"])
def test_mla_and_ssm_keep_slice_path(name):
    cfg = _cfg(name)
    params = M.init_params(jax.random.key(2), cfg)
    rt = Runtime()
    B = 4
    state = _filled_state(cfg, B, POSITIONS[B], seed=3)
    tok = jnp.arange(5, 5 + B, dtype=jnp.int32)
    lg, st = jax.jit(lambda s, t: M.decode_step(params, cfg, s, t, rt))(
        state, tok)
    lg_ref, st_ref = jax.jit(
        lambda s, t: _reference_decode_step(params, cfg, s, t, rt))(state, tok)
    _assert_states_equal(st, st_ref)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                               rtol=1e-5, atol=1e-5)
