"""The engine-counter readers: ``decode_wait_ms``, ``engine_host_ms`` and
``prefill_ms`` over a window's pair of ``engine.stats`` snapshots, and the
cells that report each."""
import pytest

from bench import harness

S0 = {"steps": 10, "decode_steps": 8, "step_s": 1.0, "wait_s": 0.6,
      "decode_wait_s": 0.5, "prefill_s": 0.2, "prefills": 2}
S1 = {"steps": 110, "decode_steps": 108, "step_s": 2.0, "wait_s": 1.4,
      "decode_wait_s": 1.2, "prefill_s": 0.5, "prefills": 5}
# an engine from before these counters: every reader finds nothing
OLD = {"steps": 110, "decode_steps": 108, "step_s": 2.0}


@pytest.mark.parametrize("name,want", [
    ("decode_wait_ms", 0.7 / 100 * 1e3),            # Δdecode_wait / Δdecode
    ("engine_host_ms", (1.0 - 0.8) / 100 * 1e3),    # (Δstep - Δwait) / Δsteps
    ("prefill_ms", 0.3 / 3 * 1e3),                  # Δprefill_s / Δprefills
    ("decode_wait_ms.saturated", 7.0),              # through the base file
    ("engine_host_ms.saturated", 2.0),
    ("prefill_ms.saturated", 100.0),
])
def test_reader_arithmetic(name, want):
    read = harness.metric_reader(name)
    assert read({"stats": [S0, S1]}) == pytest.approx(want)


@pytest.mark.parametrize("name,stop", [
    ("decode_wait_ms", "decode_steps"),
    ("engine_host_ms", "steps"),
    ("prefill_ms", "prefills"),
])
def test_zero_denominator_reads_nothing(name, stop):
    read = harness.metric_reader(name)
    assert read({"stats": [S0, dict(S1, **{stop: S0[stop]})]}) is None


@pytest.mark.parametrize("name", ["decode_wait_ms", "engine_host_ms",
                                  "prefill_ms"])
def test_engine_without_the_counters_reads_nothing(name):
    read = harness.metric_reader(name)
    assert read({"stats": [dict(OLD, steps=10, decode_steps=8), OLD]}) is None


BASE = {"decode_wait_ms", "engine_host_ms", "prefill_ms"}


@pytest.mark.parametrize("cell,want", [
    ("phi3-mini.single-stream", {"decode_wait_ms", "engine_host_ms"}),
    ("granite-3-8b-d20.single-stream", {"decode_wait_ms", "engine_host_ms"}),
    ("phi3-mini.chat-poisson", BASE),
    ("phi3-mini.chat-saturated", {f"{n}.saturated" for n in BASE}),
])
def test_cells_report_the_engine_counters(cell, want):
    names = {m["name"] for m in harness.load_cell(cell).per_layer}
    ours = BASE | {f"{n}.saturated" for n in BASE}
    assert names & ours == want
    for m in harness.load_cell(cell).per_layer:
        if m["name"] in ours:
            assert m["source"] == "program_counter" and m["layer"] == "engine"
