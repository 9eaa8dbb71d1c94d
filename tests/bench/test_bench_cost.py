"""FLOP and byte counts against the program's parameter count, the peak
table, and the benchmark's weights in the program's layout."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model_cost, weights
from bench.costs import dense_decoder as C
from bench.weights import dense_decoder as W

CONFIGS = sorted((harness.BENCH / "configs").glob("*.json"))
TINY = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 96, "vocab_size": 200,
        "mlp_type": "swiglu", "tie_embeddings": False, "rope_theta": 10000.0}
TINY_CONFIG = {"name": "tiny", "registry": "granite-3-8b", "model": TINY,
               "family_module": "dense_decoder",
               "reduced": {k: 0 for k in TINY if k not in
                           ("family", "mlp_type", "rope_theta")}}


def _config(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_param_count_matches_program(path):
    config = _config(path)
    cfg = harness.model_config(config)
    counts = model_cost.counts(config["family_module"])
    assert counts.param_count(config["model"]) == cfg.param_count()


def test_counts_by_hand():
    m = TINY
    lin = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 96
    assert C.layer_linear_params(m) == lin
    # one decode step at context 5: every weight twice, the head, and
    # 4 * H * hd per key row in each layer
    assert C.decode_flops(m, 5) == 2 * (2 * lin + 64 * 200) + 2 * 4 * 64 * 5
    # a 3-token prompt attends 1 + 2 + 3 pairs and reads the head once
    assert C.prefill_flops(m, 3) == 2 * 3 * 2 * lin + 2 * 4 * 64 * 6 \
        + 2 * 64 * 200
    assert C.kv_bytes(m, 7) == 2 * 7 * 2 * 2 * (16 + 4)
    scales = 4 * 2 * (64 + 32 + 32 + 64 + 96 + 96 + 64)
    assert C.decode_weight_bytes(m) == 2 * lin + scales + 2 * 64 * 200 \
        + 4 * 64 * 5


def test_phi3_decode_reads_its_int8_weights():
    m = _config(harness.BENCH / "configs" / "phi3-mini-3.8b.json")["model"]
    gb = C.decode_weight_bytes(m) / 1e9
    assert 3.8 < gb < 4.1          # 3.62 G int8 linears + 0.2 GB bf16 head


def test_peaks():
    p = model_cost.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        model_cost.peaks("cpu")


def test_weights_in_program_layout():
    from repro.models import model as M

    cfg = harness.model_config(TINY_CONFIG)
    params = harness.build_params(cfg, TINY_CONFIG, 5)
    want = M.abstract_params(cfg, jnp.bfloat16)
    assert jax.tree.structure(params) == jax.tree.structure(want)


def test_stacked_layer_equals_the_one_the_reference_draws():
    key = jax.random.key(9)
    params = jax.jit(lambda k: W.program_params(k, TINY))(key)
    slot = params["groups"][0][0]
    stacked = {**slot["attn"], **slot["mlp"], "ln1": slot["ln1"]["scale"],
               "ln2": slot["ln2"]["scale"]}
    for i in range(TINY["n_layers"]):
        one = W.layer_weights(key, TINY, i)
        assert set(one) == set(stacked)
        for name, leaf in one.items():
            np.testing.assert_array_equal(stacked[name][i], leaf)
    np.testing.assert_array_equal(params["lm_head"]["w"], W.head(key, TINY))
    np.testing.assert_array_equal(params["ln_f"]["scale"],
                                  W.final_norm(key, TINY))
    assert params["embed"]["w"].dtype == jnp.bfloat16


def test_derive_seed():
    a = weights.derive_seed(2**31 + 5, 1)
    assert a == weights.derive_seed(2**31 + 5, 1) != \
        weights.derive_seed(2**31 + 5, 2)
    assert 0 <= a < 2**32
