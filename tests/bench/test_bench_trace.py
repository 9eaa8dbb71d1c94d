"""Trace reduction: a trace recorded on the chip reproduces the busy time,
idle share and breakdown the chip-side run computed from it."""
import json

import pytest

from bench import harness, trace as T

FIXTURES = sorted((harness.BENCH / "testdata").glob("*.json.gz"))


def _expected(path):
    return json.loads(path.with_name(path.name.replace(".json.gz",
                                                      ".expected.json"))
                      .read_text())


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.stem)
def test_recorded_trace_reproduces_its_reduction(path):
    got, want = T.reduce(T.load(path)), _expected(path)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["idle_share"] == pytest.approx(want["idle_share"], abs=1e-12)
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[key]] == [n for n, _ in want[key]]
        assert [v for _, v in got[key]] == pytest.approx(
            [v for _, v in want[key]], rel=1e-9)
    read = harness.metric_reader("device_idle_share")
    assert read({"trace": got}) == pytest.approx(100 * want["idle_share"])
    assert 0 < got["busy_s"] <= got["window_s"]


def test_fixtures_exist_and_are_small():
    assert FIXTURES
    assert all(p.stat().st_size < 1_000_000 for p in FIXTURES)


def test_busy_is_a_union_and_ops_count_leaves():
    tr = {"window": [0, 100],
          "devices": {"/device:TPU:0": [["while", 10, 50], ["a", 10, 20],
                                        ["b", 35, 20], ["c", 60, 20]]},
          "host": [[0, "engine.step", 0, 100], [0, "engine.fetch", 85, 10],
                   [1, "server.submit", 0, 5]]}
    r = T.reduce(tr)
    assert r["busy_s"] == pytest.approx(70e-9)      # [10, 80)
    assert r["idle_share"] == pytest.approx(0.3)
    assert dict(r["device_ops"]) == pytest.approx(
        {"a": 20e-9, "b": 20e-9, "c": 20e-9})       # the loop is not a leaf
    # [0, 10) under engine.step (engine spans win over server ones),
    # [80, 100) mid 90 under engine.fetch
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"engine.step": 10e-9, "engine.fetch": 20e-9})


def test_events_are_clipped_to_the_window():
    tr = {"window": [100, 200],
          "devices": {"d": [["x", 50, 100], ["y", 190, 50]]}, "host": []}
    r = T.reduce(tr)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert dict(r["idle_gaps"]) == pytest.approx({T.NO_SPAN: 40e-9})


def test_short_op():
    text = ("%copy.11 = s8[32,1,2048,32,96]{4,3,2,1,0:T(8,128)(4,1)} "
            "copy(s8[32,1,2048,32,96]{2,4,3,1,0:T(8,128)(4,1)} %k_q.1)")
    assert T.short_op(text) == "copy.11: copy s8[32,1,2048,32,96]"
    fused = ("%fusion.86 = (s32[8]{0:T(1024)}, s32[8]{0:T(1024)}) "
             "fusion(s32[3]{0} %a), kind=kLoop, calls=%f")
    assert T.short_op(fused) == "fusion.86: fusion kLoop (s32[8], s32[8])"
    assert T.short_op("while.3") == "while.3"
