"""One benchmark run of one cell: set-up, the measured window, the check
against the float32 reference, and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``bench/configs/``); the modules of the model family
that the configuration names as ``family_module`` (weights in
``bench/weights/``, the reference in ``bench/references/``, FLOP and byte
counts in ``bench/costs/``); its traffic mix (``bench/traffic/<mix>.json``)
and the loop that mix names (``bench/loops/<loop>.py``); and one reader
per metric (``bench/metrics/<name>.py``).  The timed path is the program's
``AsyncServer.submit`` and the token streams it returns, over a
``ContinuousBatchingEngine`` built with the mix's serving settings.  All
times are the engine's monotonic clock (``ContinuousBatchingEngine.now``),
read by the clients.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import time
from pathlib import Path

import numpy as np

from bench import stats as S
from bench import weights as W
from bench.traffic import generators as G

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
DRAIN_S = 60.0                 # how long finished-window requests may take
SLOW_CALL_S = 0.5              # an engine call this long is a stall
TRACE_S = 4.0                  # a traced run profiles its window's last 4 s
CHECK_TOKENS = 512             # served tokens the reference reads at least
CHECK_REQUESTS = 8             # ... from at most this many requests
# purposes of the seeds derived from the run seed
SEED_WEIGHTS, SEED_TRAFFIC, SEED_SAMPLE, SEED_WARM = 1, 2, 3, 4


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/<mix>.json
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name, w["chips"], config, mix, e2e, per_layer)


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(record)``.  A quantity split by
    the end-to-end metric it moves (``engine_step_ms.saturated``) reads
    with its base's reader unless it has a file of its own."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(config: dict):
    """The program's registry config with the cell's cuts applied, checked
    against every width the configuration file states."""
    from repro.configs import registry

    m = config["model"]
    cfg = registry.get(config["registry"])
    cfg = dataclasses.replace(cfg, **{k: m[k] for k in config["reduced"]})
    wrong = {k: (getattr(cfg, k), v) for k, v in m.items()
             if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"program config differs from {config['name']}: "
                         f"{wrong}")
    return cfg


def build_params(cfg, config: dict, weight_seed: int):
    """Weights on the device in one jitted call, from the configuration's
    weight builder, checked against the program's own parameter shapes."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    want = M.abstract_params(cfg, jnp.bfloat16)
    family = W.family(config)
    params = jax.jit(lambda k: family.program_params(k, config["model"]))(
        jax.random.key(weight_seed))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n{got}\n{exp}")
    return jax.block_until_ready(params)


def traced_engine(cfg, params, serving: dict):
    """The program's engine with the benchmark's host spans around each
    step, each dispatch and each device fetch.  Calls that take longer than
    SLOW_CALL_S are kept in ``slow_calls`` as (name, end, seconds), on the
    engine's clock, to tell a stall inside the engine from one outside."""
    from jax.profiler import TraceAnnotation
    from repro.serve.engine import ContinuousBatchingEngine

    class Engine(ContinuousBatchingEngine):
        def __init__(self, *args, **kwargs):
            self.slow_calls = []
            super().__init__(*args, **kwargs)

        def _timed(self, name, call):
            t = time.monotonic()
            with TraceAnnotation(name):
                out = call()
            if (dt := time.monotonic() - t) > SLOW_CALL_S:
                self.slow_calls.append((name, self.now(), dt))
            return out

        def step(self):
            return self._timed("engine.step", super().step)

        def _dev(self, fn, *args):
            return self._timed("engine.dispatch",
                               lambda: super(Engine, self)._dev(fn, *args))

        def _fetch(self, x, decode=False):
            return self._timed("engine.fetch",
                               lambda: super(Engine, self)._fetch(x, decode))

    return Engine(cfg, params, n_slots=serving["n_slots"],
                  max_len=serving["max_len"],
                  prefill_bucket=serving["prefill_bucket"],
                  policy=serving["policy"])


class CompileCounter:
    """Compile work: each trace, lowering, backend compile or load from the
    persistent cache, with the time it ended on a clock that is
    ``time.monotonic() - offset`` (the engine's, without holding it)."""

    def __init__(self, offset: float):
        import jax
        self.ends: list[float] = []

        def on_duration(event, secs, **_):
            if (event.startswith("/jax/core/compile/") or event ==
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
                self.ends.append(time.monotonic() - offset)

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.ends)


@dataclasses.dataclass
class Sent:
    """One request as its client saw it."""
    prompt: list
    budget: int
    due: float
    late: float = 0.0
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    admit: float | None = None
    error: str | None = None
    stream: object = None

    def record(self) -> dict:
        return {"due": self.due, "prompt_len": len(self.prompt),
                "budget": self.budget, "times": self.times,
                "admit": self.admit, "error": self.error}


async def _serve_one(srv, clock, s: Sent) -> None:
    from jax.profiler import TraceAnnotation

    try:
        with TraceAnnotation("server.submit"):
            s.stream = await srv.submit(s.prompt, s.budget)
        async for tok in s.stream:
            s.times.append(clock())
            s.tokens.append(tok)
    except Exception as e:                        # noqa: BLE001 — recorded
        s.error = f"{type(e).__name__}: {e}"
    if s.stream is not None:
        req = s.stream.request
        s.admit = req.admit_time
        if s.error is None and (req.error or req.cancelled or req.timed_out):
            s.error = req.error or ("cancelled" if req.cancelled
                                    else "timed out")


async def _warm(srv, eng, traffic: G.Traffic, serving: dict, seed: int):
    """Every prefill bucket the mix can draw, through the served path,
    with two tokens each (one decode step)."""
    rng = np.random.default_rng(seed)
    for b in G.buckets(traffic.prefill_lengths(), serving["prefill_bucket"],
                       serving["max_len"]):
        n = min(b, serving["max_len"] - 2)
        s = Sent(rng.integers(0, traffic.vocab, n).tolist(), 2, eng.now())
        await _serve_one(srv, eng.now, s)
        if s.error or len(s.tokens) != 2:
            raise RuntimeError(f"warm-up of prefill bucket {b} failed: "
                               f"{s.error or len(s.tokens)}")


async def _trace_tail(clock, t1: float, seconds: float, timing: dict):
    """Profile the last ``seconds`` of the window: the trace's own
    ``bench.window`` span marks what was traced.  Starting the profiler
    blocks the event loop briefly; its cost is recorded."""
    import jax
    from jax.profiler import TraceAnnotation

    await asyncio.sleep(max(0.0, t1 - seconds - clock()))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t = clock()
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    timing["trace_start_s"] = clock() - t
    with TraceAnnotation("bench.window"):
        await asyncio.sleep(max(0.0, t1 - clock()))
    t = clock()
    jax.profiler.stop_trace()
    timing["trace_stop_s"] = clock() - t


async def _window(srv, eng, traffic: G.Traffic, seconds: float,
                  trace: bool) -> dict:
    """Drive the window, then let its requests finish (no new ones)."""
    clock, sent, tasks, timing = eng.now, [], [], {}
    t0 = clock()
    t1 = t0 + seconds
    stats0 = dict(eng.stats)
    tracer = (asyncio.ensure_future(
        _trace_tail(clock, t1, min(TRACE_S, seconds), timing))
        if trace else None)

    def send(i: int, due: float):
        prompt, budget = traffic.item(i)
        s = Sent(prompt, budget, due, late=clock() - due)
        sent.append(s)
        tasks.append(asyncio.ensure_future(_serve_one(srv, clock, s)))
        return tasks[-1]

    tasks.append(asyncio.ensure_future(
        traffic.loop.drive(traffic, clock, t0, t1, send)))
    await asyncio.sleep(max(0.0, t1 - clock()))
    stats1 = dict(eng.stats)
    if tracer is not None:
        await tracer
    _, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
    for s in sent:
        if s.stream is not None and not s.stream.request.done:
            s.stream.cancel()
            s.error = s.error or f"not finished {DRAIN_S:.0f} s after the window"
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for s in sent:
        s.stream = None           # a stream holds the server and its engine
    return {"sent": sent, "window": [t0, t1], "stats": [stats0, stats1],
            "timing": timing}


def sample_for_check(timeline: list[dict], sent: list[Sent],
                     window: list[float], seed: int) -> list[Sent]:
    """Finished requests due in the window, the longest first, then others
    drawn from the seed until the sample holds CHECK_TOKENS served tokens
    or CHECK_REQUESTS requests."""
    done = [s for s, r in zip(sent, timeline)
            if window[0] <= r["due"] < window[1] and not S.failed(r)]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    rest = [done[i] for i in np.random.default_rng(seed).permutation(len(done))
            if i != longest]
    out, n = [done[longest]], len(done[longest].tokens)
    for s in rest:
        if n >= CHECK_TOKENS or len(out) >= CHECK_REQUESTS:
            break
        out.append(s)
        n += len(s.tokens)
    return out


def check(config: dict, weight_seed: int, sample: list[Sent],
          max_len: int, n_failed: int,
          control: bool = False) -> tuple[bool, dict]:
    """The served tokens against the configuration's reference: the widest
    gap by which a served token's logit lies below the reference's best.
    With ``control`` the reference's low-precision control stands in the
    program's place: the gap of the token it puts first at each position."""
    ref = importlib.import_module(
        f"bench.references.{config['family_module']}")
    limit = config["check"]["max_logit_gap"]
    checks = {"failed_requests": {"value": n_failed, "limit": 0}}
    if not sample:
        checks["checked_tokens"] = {"value": 0, "limit": 1}
        return False, checks
    seqs = [(s.prompt + s.tokens, len(s.prompt)) for s in sample]
    gaps = ref.gaps(config["model"], weight_seed, seqs, max_len,
                    control=control)
    side = "control" if control else "served"
    worst = max(float(g[side].max()) for g in gaps)
    checks["max_logit_gap"] = {"value": worst, "limit": limit}
    checks["checked_tokens"] = {"value": sum(len(s.tokens) for s in sample),
                                "limit": 1}
    ok = worst <= limit and n_failed == 0
    return ok, checks


def serve(eng, traffic: G.Traffic, serving: dict, seconds: float,
          trace: bool, warm_seed: int | None) -> dict:
    """Warm (unless ``warm_seed`` is None) and drive one window through a
    fresh ``AsyncServer`` over ``eng``."""
    from repro.serve.server import AsyncServer

    async def go():
        async with AsyncServer(eng) as srv:
            if warm_seed is not None:
                await _warm(srv, eng, traffic, serving, warm_seed)
            return await _window(srv, eng, traffic, seconds, trace)

    return asyncio.run(go())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    import jax

    serving = cell.mix["serving"]
    G.check(cell.mix)
    cfg = model_config(cell.config)
    m = cell.config["model"]
    wseed = W.derive_seed(seed, SEED_WEIGHTS)
    traffic = G.Traffic(cell.mix, W.derive_seed(seed, SEED_TRAFFIC),
                        m["vocab_size"])
    params = build_params(cfg, cell.config, wseed)
    eng = traced_engine(cfg, params, serving)
    del params
    compiles = CompileCounter(time.monotonic() - eng.now())
    # process start on the engine clock: set-up ends where the window opens
    start = eng.now() - (time.monotonic() - t_start)
    out = serve(eng, traffic, serving, seconds, trace,
                W.derive_seed(seed, SEED_WARM))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    sent, window = out["sent"], out["window"]
    timeline = [s.record() for s in sent]
    in_window = S.due_in(timeline, *window)
    n_failed = sum(S.failed(r) for r in in_window)
    lateness = [s.late for s in sent]

    # free the program's state before the reference runs on the chip
    slow = [[name, end - window[0], dt] for name, end, dt in eng.slow_calls
            if window[0] <= end < window[1]]
    del eng
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    sample = sample_for_check(timeline, sent, window,
                              W.derive_seed(seed, SEED_SAMPLE))
    t_ref = time.monotonic()
    correct, checks = check(cell.config, wseed, sample,
                            serving["max_len"], n_failed)
    ref_s = time.monotonic() - t_ref

    reduction = None
    if trace:
        from bench import trace as TR
        reduction = TR.reduce(TR.load_xplane(TR.find_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    rec = {"model": m, "family_module": cell.config["family_module"],
           "window": window, "timeline": timeline,
           "stats": out["stats"], "trace": reduction,
           "setup_s": window[0] - start, "peak_bytes": peak,
           "device_kind": devices[0].device_kind, "chips": len(devices)}
    metrics = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        v = metric_reader(entry["name"])(rec)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(in_window),
              "failed": n_failed, "metrics": metrics, "device": device}
    if reduction is not None:
        device.update(busy_s=reduction["busy_s"],
                      window_s=reduction["window_s"])
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["info"] = {
        "seed": seed, "requests_sent": len(sent),
        "compiles_in_window": compiles.between(*window),
        "generator_late_p95_ms": (S.percentile(lateness, 95) or 0.0) * 1e3,
        "longest_token_gap": longest_gap(timeline, window),
        "slow_engine_calls": slow[:8],
        "reference_s": ref_s, "live_bytes_at_reference": live,
        **out["timing"],
        "setup_s": rec["setup_s"]}
    result["checks"] = checks
    return result


def longest_gap(timeline: list[dict], window: list[float]):
    """The window's longest token gap: [seconds into the window at which
    it ended, its length in ms]."""
    gaps = [(b - a, b) for r in timeline
            for a, b in zip(r["times"], r["times"][1:])
            if window[0] <= b < window[1]]
    if not gaps:
        return None
    dt, end = max(gaps)
    return [end - window[0], dt * 1e3]


def finite(x):
    """JSON-safe: a non-finite float becomes its string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x
