"""Traffic generators: seeded determinism, the clips, open-loop due times."""
import json

import numpy as np
import pytest

from bench.harness import BENCH
from bench.traffic import generators as G

MIX = {"loop": "poisson", "rate": 4.0, "deck": 64, "order_seed": 5,
       "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.9,
                  "min": 32, "max": 1536},
       "output": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                  "min": 8, "max": 256},
       "serving": {"n_slots": 4, "max_len": 2048, "prefill_bucket": 128,
                   "policy": "fifo"}}


def _requests(t, n):
    return [t.item(i) for i in range(n)]


def test_same_seed_same_requests():
    a, b = G.Traffic(MIX, 7, 1000), G.Traffic(MIX, 7, 1000)
    assert _requests(a, 80) == _requests(b, 80)
    assert [a.due(i) for i in range(80)] == [b.due(i) for i in range(80)]


def test_seeds_draw_ids_over_one_schedule_of_sizes():
    a, b = G.Traffic(MIX, 7, 1000), G.Traffic(MIX, 8, 1000)
    ra, rb = _requests(a, 64), _requests(b, 64)
    assert [p for p, _ in ra] != [p for p, _ in rb]
    assert [(len(p), o) for p, o in ra] == [(len(p), o) for p, o in rb]
    assert [a.due(i) for i in range(64)] == [b.due(i) for i in range(64)]
    # the mix's order_seed, not the run seed, orders the deck
    c = G.Traffic(dict(MIX, order_seed=6), 7, 1000)
    assert [len(p) for p, _ in _requests(c, 64)] != [len(p) for p, _ in ra]
    assert sorted(len(p) for p, _ in _requests(c, 64)) == \
        sorted(len(p) for p, _ in ra)


def test_large_seed():
    t = G.Traffic(MIX, 2**31 + 12345, 1000)
    assert len(_requests(t, 3)) == 3


@pytest.mark.parametrize("which", ["prompt", "output"])
def test_lengths_clipped_and_heavy_tailed(which):
    spec = MIX[which]
    x = G.lengths(spec, 1000)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert np.median(x) == pytest.approx(spec["median"], rel=0.02)
    assert np.mean(x) > np.median(x)             # right tail
    assert (x == spec["max"]).any() and (x == spec["min"]).any()


def test_ids_in_vocab():
    t = G.Traffic(MIX, 3, 50)
    for p, o in _requests(t, 64):
        assert 0 <= min(p) and max(p) < 50 and o >= 8


def test_open_loop_due_times():
    t = G.Traffic(MIX, 11, 1000)
    due = np.asarray([t.due(i) for i in range(3 * 64)])
    assert (np.diff(due) >= 0).all() and due[0] > 0
    # a whole deck spans its stratified mean gap exactly, and repeats
    assert due[63] == pytest.approx(np.sum(G.loop("poisson").gaps(MIX, 64)))
    assert due[64 + 5] - due[63] == pytest.approx(due[5])
    assert 64 / due[63] == pytest.approx(4.0, rel=0.1)


def test_closed_loop_has_no_due_times():
    t = G.Traffic(dict(MIX, loop="closed", clients=1), 1, 100)
    assert t.loop.gaps(t.mix, 4) is None
    with pytest.raises(TypeError):
        t.due(0)


def test_loops_are_found_by_name(monkeypatch):
    """A new loop is a new module under bench/loops: nothing else names it."""
    import sys
    import types

    from bench.loops import poisson

    assert G.loop("poisson") is poisson
    fake = types.ModuleType("bench.loops.bursty")
    fake.check = lambda mix: None
    fake.gaps = lambda mix, n: np.full(n, 0.5)
    monkeypatch.setitem(sys.modules, "bench.loops.bursty", fake)
    t = G.Traffic(dict(MIX, loop="bursty"), 1, 100)
    assert t.due(3) == pytest.approx(2.0)
    G.check(dict(MIX, loop="bursty"))


def test_buckets():
    assert G.buckets([1, 128, 129, 1536], 128, 2048) == [128, 256, 1536]
    assert G.buckets([2000], 128, 1900) == [1900]


def test_check_rejects_a_slot_overflow():
    bad = dict(MIX, serving=dict(MIX["serving"], max_len=1000))
    with pytest.raises(ValueError):
        G.check(bad)
    with pytest.raises(ValueError):
        G.check(dict(MIX, loop="no-such-loop"))
    with pytest.raises(ValueError):
        G.check(dict(MIX, rate=float("inf")))


@pytest.mark.parametrize("path", sorted((BENCH / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_mix_files(path):
    mix = json.loads(path.read_text())
    G.check(mix)
    t = G.Traffic(mix, 1, 32064)
    s = mix["serving"]
    for b in G.buckets(t.prefill_lengths(), s["prefill_bucket"], s["max_len"]):
        assert b % s["prefill_bucket"] == 0 or b == s["max_len"]
