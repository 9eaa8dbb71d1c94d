"""The serve path's tracing: every jit the engines build lowers to a named
module (``jit_<name>``, never ``jit__lambda``), the engine and server write
their spans under bare names into the profiler's trace, and the host-time
counters beside those spans add up."""
import asyncio
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.configs.registry import ARCHS
from repro.serve.engine import ContinuousBatchingEngine, Engine

jax.config.update("jax_platform_name", "cpu")
ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:          # the benchmark's trace reader
    sys.path.insert(0, str(ROOT))

# the device trace's module names, one per serve-path program
PROGRAMS = {"prefill", "prefill_chunk", "init_prefill_carry",
            "finalize_write", "copy_slot_prefix", "warm_prefill_carry",
            "decode_step", "multi_decode_step", "verify_step", "verify_tree",
            "tree_commit", "write_slot", "read_slot", "topk"}
SPANS = {"engine.step", "engine.schedule", "engine.prefill", "engine.push",
         "engine.dispatch", "engine.fetch", "engine.emit", "server.admit",
         "server.publish"}
MODULE = re.compile(r"module @(\S+)")


@pytest.fixture(scope="module")
def llama():
    from repro.models import model as M
    cfg = ARCHS["llama3-8b"].reduced()
    return cfg, M.init_params(jax.random.key(0), cfg)


def _prompts(cfg, n=5, seed=0):
    """Prompts sharing a 10-token prefix (so the prefix cache hits), with
    tails of 3-8 tokens."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, 10).tolist()
    return [shared + rng.integers(0, cfg.vocab_size,
                                  int(rng.integers(3, 9))).tolist()
            for _ in range(n)]


def _one_device_mesh_rt():
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Runtime
    return Runtime(mesh=make_mesh((1, 1), ("data", "model")),
                   data_axes=("data",))


# engine options per case, and the programs its run must dispatch
LANES = {
    "atomic": ({}, {"prefill", "write_slot", "decode_step", "topk"}),
    "chunk": ({"chunk": 4},
              {"init_prefill_carry", "prefill_chunk", "finalize_write",
               "decode_step", "topk"}),
    "prefix_cache": ({"chunk": 4, "prefix_cache": True},
                     {"copy_slot_prefix", "warm_prefill_carry"}),
    "spec": ({"spec_k": 2}, {"verify_step", "topk"}),
    "tree": ({"spec_tree": 3}, {"verify_tree", "tree_commit", "topk"}),
    "multi_step": ({"multi_step": 2}, {"multi_decode_step"}),
    "kv_swap": ({"chunk": 4, "kv_swap": True, "policy": "fair:3"},
                {"read_slot", "write_slot"}),
    "mesh": ({"chunk": 4, "prefix_cache": True, "spec_tree": 3,
              "kv_swap": True, "policy": "fair:3"},
             {"prefill_chunk", "finalize_write", "verify_tree",
              "tree_commit", "warm_prefill_carry"}),
}


@pytest.mark.parametrize("lane", list(LANES))
def test_every_serve_jit_lowers_to_its_name(llama, lane):
    """Each jit the engine builds carries a documented name, and every one
    the run dispatches lowers to ``module @jit_<name>``."""
    cfg, params = llama
    opts, must_run = LANES[lane]
    if lane == "mesh":
        opts = dict(opts, rt=_one_device_mesh_rt())
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=48,
                                   **opts)
    lowered = {}
    dispatch = eng._dev

    def lowering_dev(fn, *args):
        if hasattr(fn, "lower") and id(fn) not in lowered:
            text = fn.lower(*args).as_text()
            lowered[id(fn)] = MODULE.match(text).group(1)
        return dispatch(fn, *args)

    eng._dev = lowering_dev
    prompts = _prompts(cfg)
    # the first two requests run alone: a prefix cache then holds the
    # first one's rows in one slot, and the second lands in the other
    reqs = []
    for p in prompts[:2]:
        reqs.append(eng.submit(p, 6))
        eng.drain()
    reqs += [eng.submit(p, 6) for p in prompts[2:-1]]
    # one sampled request with a bounded top-k: the device pre-select
    reqs.append(eng.submit(prompts[-1], 6, temperature=0.8, top_k=5,
                           seed=1))
    eng.drain()
    assert all(r.error is None for r in reqs)

    built = [v for v in vars(eng).values() if hasattr(v, "lower")]
    built += list(eng._topk_fns.values())
    assert {f.__name__ for f in built} <= PROGRAMS
    names = set(lowered.values())
    assert names == {f"jit_{f.__name__}" for f in built
                     if id(f) in lowered}
    assert {f"jit_{n}" for n in must_run} <= names, names
    assert not {"jit__lambda", "jit__unknown"} & names


def test_batch_engine_jits_are_named(llama):
    cfg, params = llama
    eng = Engine(cfg, params, max_len=32)
    batch = {"inputs": jnp.ones((1, 8), jnp.int32)}
    assert MODULE.match(eng._prefill.lower(eng.params, batch).as_text()
                        ).group(1) == "jit_prefill"
    toks, _ = eng.generate(batch, 3)
    assert toks.shape == (1, 3)
    assert eng._decode.__name__ == "decode_step"


def test_server_run_writes_the_program_spans(llama, tmp_path):
    """A profiler trace of a tiny served run, read the way the benchmark
    reads it, holds each program span under its bare name and no other
    engine or server span."""
    from bench import trace as TR
    from repro.serve.server import AsyncServer, collect

    cfg, params = llama
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=48)
    prompts = _prompts(cfg, n=3)

    async def serve():
        async with AsyncServer(eng) as srv:
            streams = [await srv.submit(p, 4) for p in prompts]
            return [await collect(s) for s in streams]

    asyncio.run(serve())                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(TR.WINDOW_SPAN):
            outs = asyncio.run(serve())
    finally:
        jax.profiler.stop_trace()
    assert [len(o) for o in outs] == [4, 4, 4]
    host = TR.load_xplane(TR.find_xplane(tmp_path))["host"]
    names = {name for _, name, _, _ in host}
    assert names - {TR.WINDOW_SPAN} == SPANS


@pytest.mark.parametrize("opts", [{}, {"chunk": 4}, {"multi_step": 2},
                                  {"spec_k": 2}],
                         ids=["atomic", "chunk", "multi_step", "spec"])
def test_host_counters_add_up(llama, opts):
    cfg, params = llama
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=48,
                                   **opts)
    assert "device_s" not in eng.stats
    prompts = _prompts(cfg)
    reqs = [eng.submit(p, 5) for p in prompts]
    eng.drain()
    s = eng.stats
    assert 0 < s["decode_wait_s"] <= s["wait_s"]
    assert 0 < s["dispatch_s"]
    assert s["dispatch_s"] + s["wait_s"] <= s["step_s"]
    assert 0 < s["prefill_s"] <= s["step_s"]
    assert s["prefills"] == sum(r.first_token_time is not None
                                for r in reqs) == len(reqs)
