"""BENCHMARK.json names only what the harness can find: every cell's
configuration and mix file, a reader for every metric, a reference for
every configuration."""
import json
import re

import pytest

from bench import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_units():
    for entry in BENCH["configs"] + BENCH["workloads"] + METRICS:
        assert NAME.match(entry["name"]), entry["name"]
    for m in METRICS:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]) and \
            m["better"] in ("lower", "higher")
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    assert len({m["name"] for m in METRICS}) == len(METRICS)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = harness.load_cell(cell)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    for kind in ("references", "weights", "costs"):
        assert (harness.BENCH / kind
                / f"{c.config['family_module']}.py").is_file()
    assert c.config["name"] == next(w for w in BENCH["workloads"]
                                    if w["name"] == cell)["config"]


def test_bounds_and_moves():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_files(entry):
    config = json.loads((harness.ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert config["check"]["max_logit_gap"] > 0
    harness.model_config(config)         # the program agrees on every width
