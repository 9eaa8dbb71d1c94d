"""DeepSeek-V3's blocks at a reduced size: the group-limited sigmoid
router against a hand-worked case, one chip's expert share against the
whole layer, dropless dispatch under collisions, YaRN against its closed
form, and the MoE counters leaving programs without MoE as they were."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MoE
from repro.models.transformer import Runtime

RT = Runtime()
DS = ARCHS["deepseek-v3-671b"]


def small(**kw):
    """Reduced DeepSeek with 16 experts in 4 groups of 4, top-4 of the
    best 2 groups."""
    return dataclasses.replace(
        DS.reduced(), n_experts=16, n_experts_active=4, moe_n_group=4,
        moe_topk_group=2, **kw)


def test_reduced_keeps_a_group_structure():
    cfg = DS.reduced()
    assert cfg.n_experts % cfg.moe_n_group == 0
    assert cfg.moe_topk_group <= cfg.moe_n_group
    assert cfg.moe_scoring == "sigmoid" and cfg.yarn_factor == 40.0
    assert cfg.experts_held == cfg.n_experts


def test_group_limited_selection_by_hand():
    """8 experts in 4 groups of 2, keep 2 groups, pick 2 experts.  The
    bias moves group 3 ahead of group 1 and expert 7 ahead of 6; the
    weights are the unbiased scores, normalised, times 2.5."""
    cfg = dataclasses.replace(DS.reduced(), d_model=8, n_experts=8,
                              n_experts_active=2, moe_n_group=4,
                              moe_topk_group=2)
    s = np.array([0.9, 0.1, 0.6, 0.5, 0.2, 0.2, 0.5, 0.45], np.float32)
    bias = np.array([0, 0, 0, 0, 0, 0, 0, 0.2], np.float32)
    # group scores (top-2 sums of s + bias): 1.0, 1.1, 0.4, 1.15 -> keep
    # groups 3 and 1; candidates 2 (0.6), 3 (0.5), 6 (0.5), 7 (0.65)
    p = {"router": jnp.asarray(np.diag(np.log(s / (1 - s)))),
         "router_bias": jnp.asarray(bias)}
    x = jnp.ones((1, 8), jnp.float32)
    w, ids, _ = MoE.route(p, x, cfg)
    assert ids.tolist() == [[7, 2]]
    np.testing.assert_allclose(np.asarray(w[0]),
                               2.5 * np.array([0.45, 0.6]) / 1.05, rtol=1e-5)


def test_shares_add_up_to_the_whole_layer():
    """Each of 4 shards holds 4 of 16 experts; their outputs, the shared
    expert counted once, sum to the uncut layer."""
    cfg = small()
    p = MoE.moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 5, cfg.d_model))
    whole, _, whole_c = MoE.moe_share(p, x, cfg)
    shared = L.apply_mlp(p["shared"], x.reshape(10, -1), cfg.mlp_type)
    total, pairs = 0.0, 0
    for shard in range(4):
        part_cfg = dataclasses.replace(cfg, n_experts_held=4,
                                       expert_shard=shard)
        part = {k: (v[4 * shard:4 * shard + 4]
                    if k in ("w_up", "w_gate", "w_down") else v)
                for k, v in p.items()}
        out, _, c = MoE.moe_share(part, x, part_cfg)
        total = total + out.reshape(10, -1) - shared
        pairs += int(c["local_pairs"])
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole.reshape(10, -1)),
                               rtol=1e-4, atol=1e-5)
    assert pairs == int(whole_c["local_pairs"]) == 10 * cfg.n_experts_active


@pytest.mark.parametrize("quantized", [False, True])
def test_colliding_tokens_drop_nothing(quantized):
    """Eight identical tokens all pick the same experts: every one of
    them gets the single token's output (a capacity of 2 per expert
    would have dropped six)."""
    from repro.serve.quantize import _quantize_3d

    cfg = small()
    p = MoE.moe_init(jax.random.key(2), cfg)
    if quantized:
        for nm in ("w_up", "w_gate", "w_down"):
            p[nm + "_q"], p[nm + "_s"] = _quantize_3d(p.pop(nm))
    one = jax.random.normal(jax.random.key(3), (1, cfg.d_model))
    out, _, c = MoE.moe_local_counted(p, jnp.tile(one, (8, 1)), cfg)
    alone, _ = MoE.moe_local(p, one, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(np.asarray(alone), out.shape),
                               rtol=1e-5, atol=1e-6)
    assert int(c["local_pairs"]) == 8 * cfg.n_experts_active
    assert int(c["experts_touched"]) == cfg.n_experts_active


def test_yarn_closed_form():
    """DeepSeek-V3's rope dims: the plain frequency below pair 10, a
    fortieth of it from pair 23, a linear mix between; the softmax scale
    (0.1 ln 40 + 1)^2 / sqrt(192)."""
    f = A.mla_rope_freqs(DS)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(f, base * (1 - ramp) + base / 40 * ramp,
                               rtol=1e-6)
    assert f[10] == pytest.approx(base[10], rel=1e-6)
    assert f[23] == pytest.approx(base[23] / 40, rel=1e-6)
    want = (0.1 * math.log(40) + 1) ** 2 / math.sqrt(192)
    assert A.mla_softmax_scale(DS) == pytest.approx(want)
    assert A.mla_softmax_scale(dataclasses.replace(DS, yarn_factor=0.0)) \
        == pytest.approx(1 / math.sqrt(192))
    assert A.mla_rope_freqs(dataclasses.replace(DS, yarn_factor=0.0)) is None


def test_counters_leave_programs_without_moe_as_they_were():
    """A dense stack's decode step lowers to the same program whether or
    not the counters are asked for, and the engine does not ask."""
    from repro.serve.engine import ContinuousBatchingEngine

    cfg = ARCHS["llama3-8b"].reduced()
    p = M.init_params(jax.random.key(0), cfg)
    st = M.init_decode_state(cfg, 2, 16)
    tok = jnp.zeros((2,), jnp.int32)
    plain = jax.jit(lambda p, s, t: M.decode_step(p, cfg, s, t, RT))
    counted = jax.jit(lambda p, s, t: M.decode_step(p, cfg, s, t, RT,
                                                    counts=True)[:2])
    assert plain.lower(p, st, tok).as_text() == \
        counted.lower(p, st, tok).as_text()
    eng = ContinuousBatchingEngine(cfg, p, n_slots=2, max_len=16)
    assert not eng._moe_counts and not any(k.startswith("moe")
                                           for k in eng.stats)
    out = jax.eval_shape(eng._decode, eng.qparams, eng.state, tok)
    assert len(out) == 2


def test_engine_counts_routed_pairs():
    """Through the engine: every prompt token's pairs at prefill and every
    slot's pairs at decode land on the (all-held) experts."""
    from repro.serve.engine import ContinuousBatchingEngine

    cfg = DS.reduced()
    p = M.init_params(jax.random.key(0), cfg)
    eng = ContinuousBatchingEngine(cfg, p, n_slots=2, max_len=32,
                                   prefill_bucket=8)
    reqs = [eng.submit(list(range(1, 1 + n)), 3) for n in (5, 7)]
    while not all(r.done for r in reqs):
        eng.step()
    n_moe = cfg.n_layers - cfg.first_dense_layers
    k = cfg.n_experts_active
    assert eng.stats["moe_prefill_local_pairs"] == 12 * k * n_moe
    assert eng.stats["moe_local_pairs"] == \
        eng.stats["decode_steps"] * 2 * k * n_moe
    assert 0 < eng.stats["moe_experts_touched"] <= \
        eng.stats["decode_steps"] * n_moe * cfg.n_experts
