"""The comparison that decides ``correct``, driven through the rest of a
run at a size the CPU holds: a sound run passes, the int4 control fails
the same comparison, and so does the served path broken underneath (a
token altered where it is produced; a decode step that hands back its K/V
state unchanged; half of the batch's slots decoded from the wrong token)."""
import json
import time

import jax
import pytest

from bench import control, harness

PHI3 = json.loads((harness.BENCH / "configs" / "phi3-mini-3.8b.json")
                  .read_text())
LIMIT = PHI3["check"]["max_logit_gap"]
MODEL = {"family": "dense", "n_layers": 4, "d_model": 128, "n_heads": 4,
         "n_kv_heads": 4, "head_dim": 32, "d_ff": 256, "vocab_size": 512,
         "mlp_type": "swiglu", "tie_embeddings": False, "rope_theta": 10000.0}
SEED = 2**31 + 77


def tiny_cell(loop: str, gqa_tied: bool = False) -> harness.Cell:
    """Phi-3's MHA with an untied head, or Granite's GQA with a tied one."""
    model = (dict(MODEL, n_kv_heads=2, tie_embeddings=True) if gqa_tied
             else MODEL)
    config = {"name": "tiny",
              "registry": "granite-3-8b" if gqa_tied else PHI3["registry"],
              "family_module": PHI3["family_module"], "model": model,
              "reduced": {k: 0 for k in ("n_layers", "d_model", "n_heads",
                                         "n_kv_heads", "head_dim", "d_ff",
                                         "vocab_size")},
              "check": PHI3["check"]}
    mix = {"loop": loop, "clients": 1, "rate": 40.0, "deck": 8,
           "order_seed": 3,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 8, "max": 48},
           "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 4, "max": 16},
           "serving": {"n_slots": 1 if loop == "closed" else 3,
                       "max_len": 64, "prefill_bucket": 16,
                       "policy": "fifo"}}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.Cell("tiny", 1, config, mix, bench["end_to_end"], [])


def run(cell):
    return harness.run_cell(cell, SEED, 1.0, False, time.monotonic(),
                            jax.devices()[:1])


@pytest.mark.parametrize("loop,gqa_tied", [("closed", False),
                                           ("poisson", True)])
def test_sound_run_is_correct(loop, gqa_tied):
    r = run(tiny_cell(loop, gqa_tied))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["checked_tokens"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert "tpot_p95_ms" in r["metrics"]


def test_int4_control_fails():
    """Through ``harness.check``: the program is correct and the control,
    in its place at the same positions, is not.  The open loop makes the
    checked requests the same however fast this machine serves them."""
    rows = control.readings(tiny_cell("poisson"), [1, 2, 3], 1.0,
                            jax.devices()[:1])
    for row in rows:
        assert row["program_correct"] and not row["control_correct"], row
        assert row["program"] <= LIMIT < row["control"], row


def _token_altered(eng):
    nxt = eng._next_tokens
    eng._next_tokens = lambda logits, dec: (nxt(logits, dec) + 1) % 512


def _state_unchanged(eng):
    from repro.models import model as M
    eng._decode = jax.jit(lambda p, s, t: (
        M.decode_step(p, eng.cfg, s, t, eng.rt)[0], s))


def _half_batch_left_out(eng):
    from repro.models import model as M
    eng._decode = jax.jit(lambda p, s, t: M.decode_step(
        p, eng.cfg, s, t.at[t.shape[0] // 2:].set(0), eng.rt))


@pytest.mark.parametrize("fault,loop", [(_token_altered, "closed"),
                                        (_state_unchanged, "closed"),
                                        (_half_batch_left_out, "poisson")],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_broken_served_path_is_not_correct(fault, loop, monkeypatch):
    make = harness.traced_engine

    def broken(*a, **kw):
        eng = make(*a, **kw)
        fault(eng)
        return eng

    monkeypatch.setattr(harness, "traced_engine", broken)
    r = run(tiny_cell(loop))
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > LIMIT
