"""The DeepSeek-V3 family's benchmark modules at a size the CPU holds: the
weight builder in the program's layout, the reference and its int4 control
through the harness's own comparison, the count module by hand, and the
MoE readers on a synthetic record."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness
from bench.costs import deepseek_v3 as C
from bench.weights import deepseek_v3 as W

DS = json.loads((harness.BENCH / "configs" / "deepseek-v3-d7.json")
                .read_text())
# the published widths' limit comes from chip readings; these widths put
# logits on another scale, where the dense configurations' 1.2 separates
# the program (0.06-0.86 on six seeds) from the int4 control (1.8-3.3)
TINY_CHECK = {"max_logit_gap": 1.2}
TINY = dict(DS["model"], n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
            d_ff=96, vocab_size=256, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_experts=16, n_experts_active=4, moe_d_ff=32,
            first_dense_layers=1, moe_n_group=4, moe_topk_group=2,
            n_experts_held=4, expert_shard=1, yarn_original_max_pos=64)
SEED = 2**31 + 91


def tiny_config(**model) -> dict:
    m = dict(TINY, **model)
    return {"name": "tiny-ds", "registry": DS["registry"],
            "family_module": DS["family_module"], "model": m,
            "reduced": {k: 0 for k in m
                        if k not in ("family", "attn_type", "mlp_type")},
            "check": TINY_CHECK}


def tiny_cell() -> harness.Cell:
    """An open loop, so that the checked requests are the same however
    fast this machine serves them."""
    mix = {"loop": "poisson", "rate": 6.0, "deck": 6, "order_seed": 5,
           "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                      "min": 8, "max": 40},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                      "min": 4, "max": 12},
           "serving": {"n_slots": 3, "max_len": 64, "prefill_bucket": 16,
                       "policy": "fifo"}}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.Cell("tiny-ds", 1, tiny_config(), mix, bench["end_to_end"],
                        [])


def test_weights_in_program_layout():
    from repro.models import model as M

    config = tiny_config()
    cfg = harness.model_config(config)
    assert cfg.experts_held == 4 and cfg.expert_shard == 1
    params = harness.build_params(cfg, config, 5)      # checks the layout
    want = M.abstract_params(cfg, jnp.bfloat16)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert C.param_count(config["model"]) == cfg.param_count()


def test_expert_drawn_from_its_global_id():
    """Shard 1 holds experts 4..7; expert 5 is the same array whichever
    shard holds it, and the stack's other rows are other experts."""
    key = jax.random.key(3)
    p1 = jax.jit(lambda k: W.program_params(k, TINY))(key)
    p0 = jax.jit(lambda k: W.program_params(k, dict(TINY, expert_shard=0,
                                                    n_experts_held=8)))(key)
    moe1, moe0 = p1["groups"][1][0]["moe"], p0["groups"][1][0]["moe"]
    np.testing.assert_array_equal(moe1["w_up"][:, 1], moe0["w_up"][:, 5])
    one = W.expert_weights(key, TINY, 1, 5)
    np.testing.assert_array_equal(moe1["w_down"][0, 1], one["w_down"])
    assert not np.array_equal(moe1["w_up"][:, 0], moe1["w_up"][:, 1])


def test_sound_run_is_correct_and_control_is_not():
    """A served tiny DeepSeek passes the harness's comparison; the int4
    control, in its place at the same positions, fails it."""
    cell = tiny_cell()
    r = harness.run_cell(cell, SEED, 1.0, False, time.monotonic(),
                         jax.devices()[:1])
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["checks"]["checked_tokens"]["value"] > 0
    rows = control.readings(cell, [1, 2], 1.0, jax.devices()[:1])
    for row in rows:
        assert row["program_correct"] and not row["control_correct"], row


def test_counts_by_hand():
    m = TINY
    d, h, r, ql = 64, 4, 16, 32
    attn = d * ql + ql * h * 24 + d * 24 + r * h * 32 + h * 16 * d
    assert C.attn_params(m) == attn
    expert = 3 * d * 32
    # one dense and two MoE layers; the router has a bias per expert
    assert C.param_count(m) == (2 * 256 * d + attn + 3 * d * 96 + 2 * d
                                + 2 * (attn + 16 * (d + 1) + expert
                                       + 4 * expert + 2 * d))
    tok = 3 * attn + 3 * d * 96 + 2 * (expert + d * 16)
    assert C.dense_token_params(m) == tok
    # a decode token at context 5: absorbed scores over latent + rope and
    # the latent sum, per head and row, in all 3 layers
    assert C.decode_flops(m, 5) == 2 * (tok + d * 256) \
        + 3 * 2 * h * (2 * r + 8) * 5
    # a 3-token prompt: 6 (query, key) pairs of (16 + 8 + 16) dims per head
    assert C.prefill_flops(m, 3) == 2 * 3 * tok + 3 * 2 * h * 40 * 6 \
        + 2 * d * 256
    assert C.expert_pair_flops(m) == 2 * expert
    assert C.expert_bytes(m) == expert + 2 * (2 * 32 + d)
    assert C.latent_bytes(m, 7) == 3 * 7 * (16 + 8 + 4)


S0 = {"decode_steps": 10, "moe_local_pairs": 100, "moe_experts_touched": 40,
      "moe_prefill_local_pairs": 500}
S1 = {"decode_steps": 30, "moe_local_pairs": 260, "moe_experts_touched": 100,
      "moe_prefill_local_pairs": 900}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _rec(stats=(S0, S1)):
    # one request: prompt of 10, a first token and two decoded tokens in
    # the window [0, 2), one decoded token after it
    return {"model": TINY, "family_module": "deepseek_v3",
            "window": [0.0, 2.0], "stats": list(stats),
            "timeline": [{"prompt_len": 10, "times": [0.5, 1.0, 1.5, 2.5]}],
            "device_kind": "TPU v5 lite", "chips": 1}


def test_moe_readers():
    rec = _rec()
    # 160 pairs over 20 steps, 2 MoE layers, 4 held experts
    assert harness.metric_reader("moe_expert_load")(rec) == \
        pytest.approx(160 / (20 * 2 * 4))
    want = (20 * C.decode_fixed_bytes(TINY) + 60 * C.expert_bytes(TINY)
            + C.latent_bytes(TINY, 11) + C.latent_bytes(TINY, 12))
    assert harness.metric_reader("moe_mbu")(rec) == \
        pytest.approx(100 * want / (2.0 * PEAK["hbm_bytes_per_s"]))
    flops = (C.prefill_flops(TINY, 10) + C.decode_flops(TINY, 11)
             + C.decode_flops(TINY, 12) + (160 + 400) * C.expert_pair_flops(TINY))
    assert harness.metric_reader("moe_mfu")(rec) == \
        pytest.approx(100 * flops / (2.0 * PEAK["bf16_flops_per_s"]))


@pytest.mark.parametrize("name", ["moe_expert_load", "moe_mbu", "moe_mfu"])
def test_moe_readers_find_nothing_without_the_counters(name):
    old = {"decode_steps": 30}
    assert harness.metric_reader(name)(
        _rec(({"decode_steps": 10}, old))) is None


def test_cells_report_the_moe_readers():
    names = {m["name"] for m in
             harness.load_cell("deepseek-v3-d7.closed-32").per_layer}
    assert {"moe_expert_load", "moe_mbu", "moe_mfu", "engine_step_ms",
            "decode_wait_ms", "engine_host_ms", "device_idle_share"} <= names
    for cell in ("phi3-mini.single-stream", "granite-3-8b-d20.chat-poisson"):
        got = {m["name"] for m in harness.load_cell(cell).per_layer}
        assert not got & {"moe_expert_load", "moe_mbu", "moe_mfu"}


def test_engine_prefill_and_cached_decode_match_the_reference():
    """Atomic prefill, then decode through the int8 latent pool, in the
    continuous-batching engine (float weights, two slots at different
    positions): every logits row the engine sampled from lies within the
    latent pool's int8 rounding of the float32 reference's full forward
    at that position."""
    from bench.references import deepseek_v3 as REF
    from repro.serve.engine import ContinuousBatchingEngine

    config = tiny_config()
    cfg = harness.model_config(config)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          harness.build_params(cfg, config, 7))
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                   quantize=False, prefill_bucket=16)
    rows: dict[int, list] = {}
    first, nxt = eng._first_token, eng._next_tokens

    def spy_first(req, logits):
        rows.setdefault(req.rid, []).append(np.asarray(logits)[0])
        return first(req, logits)

    def spy_next(logits, dec):
        for slot, req in dec:
            rows[req.rid].append(np.asarray(logits)[slot])
        return nxt(logits, dec)

    eng._first_token, eng._next_tokens = spy_first, spy_next
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, 256, n).tolist(), 6) for n in (11, 29)]
    while not all(r.done for r in reqs):
        eng.step()
    for req in reqs:
        seq = req.prompt + req.output
        want = REF.logits(config["model"], 7, seq)[len(req.prompt) - 1:-1]
        got = np.stack(rows[req.rid])
        assert got.shape == want.shape
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < 0.03, err
        assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.8
