"""Async streaming serve front-end: stream/generate_all parity, disconnect
slot recycling (mid-decode, mid-chunked-prefill, mid-spec-window),
bounded-queue backpressure, drain/cancel hygiene, and the monotonic
metrics clock."""
import asyncio
import time

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.serve.scheduler import RequestState

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    from repro.models import model as M
    cfg = ARCHS["llama3-8b"].reduced()
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    from repro.serve.engine import ContinuousBatchingEngine
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    return ContinuousBatchingEngine(cfg, params, **kw)


def _trace(cfg, n=5, seed=3, max_prompt=12, max_new=6):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            rng.integers(3, max_prompt + 1)).tolist()
               for _ in range(n)]
    budgets = [int(rng.integers(2, max_new + 1)) for _ in range(n)]
    return prompts, budgets


def _stream_all(eng, prompts, budgets, stream_buffer=4, **submit_kw):
    """Submit everything to a live server and collect every stream."""
    from repro.serve.server import AsyncServer, collect

    async def run():
        async with AsyncServer(eng, stream_buffer=stream_buffer) as srv:
            streams = [await srv.submit(p, b, **submit_kw)
                       for p, b in zip(prompts, budgets)]
            return [list(o) for o in
                    await asyncio.gather(*(collect(s) for s in streams))]

    return asyncio.run(run())


class TestStreamParity:
    """The async front-end must never perturb what the engine emits: the
    streamed tokens are the same list ``generate_all`` would return on an
    identically-configured engine, for every scheduling policy."""

    @pytest.mark.parametrize("policy",
                             ["fifo", "sjf", "priority:preempt", "fair:3"])
    def test_stream_matches_generate_all(self, setup, policy):
        cfg, params = setup
        prompts, budgets = _trace(cfg)
        ref = _engine(cfg, params, policy=policy).generate_all(
            prompts, budgets)
        got = _stream_all(_engine(cfg, params, policy=policy),
                          prompts, budgets)
        assert got == ref

    def test_stream_parity_chunked_and_speculative(self, setup):
        """Chunked prefill + the spec-decode lane under the server: the
        pending handoff and pump scheduling must not disturb chunk
        interleaving or verify/rollback."""
        cfg, params = setup
        prompts, budgets = _trace(cfg, seed=5)
        kw = dict(chunk=3, spec_tree=4, spec_branch=1, policy="sjf")
        ref = _engine(cfg, params, **kw).generate_all(prompts, budgets)
        eng = _engine(cfg, params, **kw)
        got = _stream_all(eng, prompts, budgets, stream_buffer=2)
        assert got == ref
        assert eng.stats["chunks"] > 0 and eng.stats["verify_steps"] > 0


class TestDisconnect:
    """A disconnect frees the slot at the next iteration boundary and the
    next queued request is admitted into it; the cancelled request keeps
    its partial output and ends CANCELLED."""

    def test_cancel_mid_decode_frees_slot_for_queued(self, setup):
        from repro.serve.server import AsyncServer, collect
        cfg, params = setup
        p1, p2 = [1, 2, 3, 4, 5], [9, 8, 7, 6]
        ref2 = _engine(cfg, params, n_slots=1).generate_all([p2], [4])[0]

        eng = _engine(cfg, params, n_slots=1)

        async def run():
            async with AsyncServer(eng, stream_buffer=4) as srv:
                s1 = await srv.submit(p1, 8)
                s2 = await srv.submit(p2, 4)     # queued behind s1
                got1 = []
                async for tok in s1:
                    got1.append(tok)
                    if len(got1) == 2:
                        s1.cancel()              # disconnect mid-decode
                got2 = await collect(s2)
                return s1, got1, got2

        s1, got1, got2 = asyncio.run(run())
        assert s1.cancelled and s1.request.state is RequestState.CANCELLED
        assert len(s1.request.output) >= 2       # partial output kept
        assert got2 == ref2                      # admitted into freed slot
        assert not eng.scheduler.has_work() and not eng._carries

    def test_cancel_mid_chunked_prefill_drops_carry(self, setup):
        cfg, params = setup
        pA = list(range(1, 13))                  # 6 chunks of 2
        pB = [5, 4, 3, 2]
        ref = _engine(cfg, params, n_slots=1,
                      chunk=2).generate_all([pB], [4])[0]
        eng = _engine(cfg, params, n_slots=1, chunk=2)
        rA = eng.submit(pA, 4)
        rB = eng.submit(pB, 4)
        eng.step()                               # A mid-prefill, carry live
        assert rA.state is RequestState.PREFILLING and eng._carries
        eng.cancel(rA)
        eng.drain()
        assert rA.state is RequestState.CANCELLED and rA.output == []
        assert not eng._carries                  # float carry dropped
        assert rB.output == ref

    def test_cancel_between_spec_windows(self, setup):
        cfg, params = setup
        pA, pB = [2, 4, 6, 8, 10, 12], [11, 3, 5, 9]
        ref = _engine(cfg, params, n_slots=1, spec_tree=4,
                      spec_branch=1).generate_all([pB], [5])[0]
        eng = _engine(cfg, params, n_slots=1, spec_tree=4, spec_branch=1)
        rA = eng.submit(pA, 12)
        rB = eng.submit(pB, 5)
        while len(rA.output) < 2:                # at least one verify window
            eng.step()
        eng.cancel(rA)
        eng.drain()
        assert rA.state is RequestState.CANCELLED
        assert 2 <= len(rA.output) < 12          # partial, mid-budget
        # the freed rows were reused without a rewind: B is exact
        assert rB.output == ref
        assert not eng.scheduler.has_work()

    def test_cancel_queued_request_never_runs(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, n_slots=1)
        rA = eng.submit([1, 2, 3], 3)
        rB = eng.submit([4, 5, 6], 3)            # still queued
        eng.cancel(rB)
        eng.drain()
        assert rB.state is RequestState.CANCELLED and rB.output == []
        assert rB.slot is None
        assert rA.state is RequestState.FINISHED and len(rA.output) == 3


class TestDrainHygiene:
    """drain() must terminate — not spin — when every remaining request
    has failed or been cancelled."""

    def test_drain_terminates_after_failing_queued_request(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, n_slots=1)
        rA = eng.submit([1, 2, 3], 2)
        rB = eng.submit([4, 5, 6], 2)
        # regression: fail() used to leave a QUEUED request in the queue,
        # so has_work() stayed true and drain() spun forever
        eng.scheduler.fail(rB, error="client gone")
        eng.drain()
        assert rA.state is RequestState.FINISHED and len(rA.output) == 2
        assert rB.error == "client gone" and rB.done

    def test_admission_failure_frees_slot_and_carry(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, n_slots=1, chunk=2)
        real = eng._chunk_fn
        calls = {"n": 0}

        def exploding(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:                  # die mid-prefill, carry live
                raise RuntimeError("RESOURCE_EXHAUSTED: synthetic OOM")
            return real(*a, **kw)

        eng._chunk_fn = exploding
        rA = eng.submit(list(range(1, 11)), 3)
        rB = eng.submit([7, 8, 9], 3)
        eng.drain()
        assert rA.error is not None and rA.done
        assert not eng._carries                  # _fail dropped the carry
        assert rB.state is RequestState.FINISHED and len(rB.output) == 3


class TestBackpressure:
    """A consumer that stops reading parks its own pump at the queue bound;
    the step loop and every other stream keep going."""

    def test_slow_consumer_does_not_stall_step_loop(self, setup):
        from repro.serve.server import AsyncServer, collect
        cfg, params = setup
        p1, p2 = [1, 2, 3, 4], [5, 6, 7, 8]
        eng = _engine(cfg, params, n_slots=2)

        async def run():
            async with AsyncServer(eng, stream_buffer=1) as srv:
                slow = await srv.submit(p1, 6)
                fast = await srv.submit(p2, 6)
                fast_toks = await collect(fast)  # never touch `slow`
                # the engine must finish both requests even though slow's
                # queue has been full since its first token
                while not slow.request.done:
                    await asyncio.sleep(0.005)
                assert slow._pumped < len(slow.request.output)
                slow_toks = await collect(slow)  # late reader gets it all
                return fast_toks, slow_toks

        fast_toks, slow_toks = asyncio.run(run())
        assert len(fast_toks) == 6 and len(slow_toks) == 6
        ref = _engine(cfg, params, n_slots=2).generate_all([p1, p2], [6, 6])
        assert [slow_toks, fast_toks] == ref

    def test_zero_buffer_rejected(self, setup):
        from repro.serve.server import AsyncServer
        cfg, params = setup
        with pytest.raises(ValueError):
            AsyncServer(_engine(cfg, params), stream_buffer=0)


class TestServerLifecycle:
    def test_stop_cancels_inflight_and_rejects_new(self, setup):
        from repro.serve.server import AsyncServer
        cfg, params = setup
        eng = _engine(cfg, params, n_slots=1)

        async def run():
            srv = AsyncServer(eng, stream_buffer=4)
            await srv.start()
            s = await srv.submit([1, 2, 3], 12)
            await s.__anext__()                  # at least one token out
            await srv.stop()
            assert s.request.done                # cancelled by shutdown
            with pytest.raises(RuntimeError):
                await srv.submit([4, 5], 2)
            return s

        s = asyncio.run(run())
        assert s.request.state is RequestState.CANCELLED
        assert not eng.scheduler.has_work() and not eng._carries

    def test_invalid_submit_raises_at_caller(self, setup):
        from repro.serve.server import AsyncServer
        cfg, params = setup
        eng = _engine(cfg, params, max_len=16)

        async def run():
            async with AsyncServer(eng) as srv:
                with pytest.raises(ValueError):
                    await srv.submit(list(range(30)), 8)   # oversized
                ok = await srv.submit([1, 2, 3], 2)        # server survives
                return [t async for t in ok]

        assert len(asyncio.run(run())) == 2


class TestMonotonicClock:
    def test_request_timestamps_ordered(self, setup):
        """arrival <= admit <= first token <= finish on one shared
        monotonic timebase, for batch-drained and streamed requests."""
        cfg, params = setup
        prompts, budgets = _trace(cfg, n=4)
        eng = _engine(cfg, params)
        rs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.drain()
        from repro.serve.server import AsyncServer, collect

        async def run(eng2):
            async with AsyncServer(eng2) as srv:
                streams = [await srv.submit(p, b)
                           for p, b in zip(prompts, budgets)]
                await asyncio.gather(*(collect(s) for s in streams))
                return [s.request for s in streams]

        rs += asyncio.run(run(_engine(cfg, params)))
        for r in rs:
            assert 0.0 <= r.arrival_time <= r.admit_time
            assert r.admit_time <= r.first_token_time <= r.finish_time

    def test_clock_immune_to_wall_clock_skew(self, setup, monkeypatch):
        """The engine timebase is time.monotonic: stepping the wall clock
        (NTP skew) must not move it."""
        cfg, params = setup
        eng = _engine(cfg, params)
        before = eng.now()
        monkeypatch.setattr(time, "time", lambda: -1e9)   # wall clock jumps
        after = eng.now()
        assert after >= before                    # still monotonic, still sane
        assert after < before + 60.0

    def test_reset_clock_rezeros(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params)
        time.sleep(0.01)
        assert eng.now() > 0.0
        eng.reset_clock()
        assert eng.now() < 0.01 + 1.0


class TestServeLoopFault:
    """An exception escaping ``engine.step()`` on the worker thread must
    not strand consumers: every live TokenStream gets a terminal failure,
    later submissions are rejected loudly, and ``stop()`` re-raises the
    loop's exception (DESIGN §1j)."""

    def test_step_exception_fails_all_live_streams(self, setup):
        from repro.serve.engine import RequestFailedError
        from repro.serve.server import AsyncServer

        cfg, params = setup
        # long budgets: every stream must still be live when the loop dies
        prompts, budgets = [[1, 2, 3]] * 3, [20] * 3
        eng = _engine(cfg, params)
        orig = eng.step

        def flaky():
            # deterministic trigger: die on the first step that sees the
            # whole trace admitted (no race with the submit handoff)
            if len(eng.scheduler.queue) + len(eng.scheduler.active) >= 3:
                raise RuntimeError("device on fire")
            return orig()

        eng.step = flaky

        async def run():
            srv = AsyncServer(eng, stream_buffer=4)
            await srv.start()
            streams = [await srv.submit(p, b)
                       for p, b in zip(prompts, budgets)]
            failed = 0
            for s in streams:
                with pytest.raises(RequestFailedError):
                    async for _ in s:
                        pass
                failed += 1
            with pytest.raises(RuntimeError,
                               match="serve loop has terminated"):
                await srv.submit(prompts[0], 2)
            with pytest.raises(RuntimeError, match="device on fire"):
                await srv.stop()
            return failed, streams

        failed, streams = asyncio.run(run())
        assert failed == len(streams) == 3

    def test_deadline_stream_raises_request_timed_out(self, setup):
        from repro.serve.server import AsyncServer, RequestTimedOut

        cfg, params = setup
        prompts, budgets = _trace(cfg, n=2)
        eng = _engine(cfg, params)

        async def run():
            async with AsyncServer(eng, stream_buffer=4) as srv:
                fast = await srv.submit(prompts[0], budgets[0])
                late = await srv.submit(prompts[1], budgets[1],
                                        deadline_s=1e-6)
                toks = []
                async for t in fast:
                    toks.append(t)
                with pytest.raises(RequestTimedOut):
                    async for _ in late:
                        pass
                assert late.timed_out and not late.cancelled
                assert late.request.state is RequestState.TIMEOUT
                assert len(toks) == budgets[0]    # healthy stream unharmed

        asyncio.run(run())
        assert eng.stats["timeouts"] == 1
        assert not eng.scheduler.has_work()
