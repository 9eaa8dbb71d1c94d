"""End-to-end arithmetic: percentiles over every sample, window rates,
failed requests counted as failed."""
import math

import numpy as np
import pytest

from bench import harness, stats as S


def req(due, times, budget=None, error=None, admit=None, prompt_len=10):
    return {"due": due, "times": times, "budget": budget or len(times),
            "error": error, "admit": admit, "prompt_len": prompt_len}


def rec(timeline, window=(0.0, 10.0), **kw):
    return {"timeline": timeline, "window": list(window), **kw}


def test_percentile_is_over_every_sample():
    x = np.random.default_rng(0).random(1001)
    assert S.percentile(x, 95) == pytest.approx(np.percentile(x, 95))
    assert S.percentile([], 95) is None
    assert S.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert math.isinf(S.percentile([1.0, 2.0, math.inf], 95))


def test_failed():
    assert not S.failed(req(0, [1, 2]))
    assert S.failed(req(0, [1], budget=2))             # cut short
    assert S.failed(req(0, [1, 2], error="boom"))
    assert S.failed(req(0, [], budget=1))              # never a token


def test_token_gaps_end_in_the_window():
    tl = [req(0.0, [1.0, 2.0, 9.5, 10.5]), req(5.0, [6.0, 6.5])]
    assert sorted(S.token_gaps(tl, 0.0, 10.0)) == [0.5, 1.0, 7.5]


def test_ttft_from_due_time_and_failures_infinite():
    tl = [req(1.0, [1.5, 2.0]), req(2.0, [], budget=3),
          req(3.0, [4.0], error="x"), req(11.0, [12.0])]
    got = S.ttfts(tl, 0.0, 10.0)
    assert got[0] == 0.5 and math.isinf(got[1]) and math.isinf(got[2])
    assert len(got) == 3                                # due outside: out


def test_a_tail_of_failures_is_infinite():
    ok = [req(i * 0.1, [i * 0.1 + 0.2]) for i in range(40)]
    assert S.percentile(S.ttfts(ok, 0, 10), 95) == pytest.approx(0.2)
    bad = ok + [req(5.0, [], budget=1)] * 5
    assert math.isinf(S.percentile(S.ttfts(bad, 0, 10), 95))


def test_rates_over_the_window():
    tl = [req(0.0, [0.5 * k for k in range(1, 30)])]   # 0.5 .. 14.5 s
    # 19 tokens arrive in [0, 10): 0.5 .. 9.5
    assert harness.metric_reader("tokens_per_s")(rec(tl)) == pytest.approx(1.9)
    gaps = harness.metric_reader("tpot_p95_ms")(rec(tl))
    assert gaps == pytest.approx(500.0)


def test_queue_wait_and_engine_step():
    tl = [req(1.0, [2.0], admit=1.25), req(2.0, [3.0], admit=None)]
    assert S.queue_waits(tl, 0, 10)[0] == 0.25
    assert math.isinf(S.queue_waits(tl, 0, 10)[1])
    step = harness.metric_reader("engine_step_ms")
    r = {"stats": [{"steps": 10, "step_s": 1.0}, {"steps": 30, "step_s": 1.5}]}
    assert step(r) == pytest.approx(25.0)


def test_device_idle_share_reads_only_a_trace():
    read = harness.metric_reader("device_idle_share")
    assert read({"trace": None}) is None
    assert read({"trace": {"idle_share": 0.25}}) == 25.0


def test_finite_keeps_json_valid():
    assert harness.finite({"a": [math.inf, 1.0]}) == {"a": ["inf", 1.0]}
