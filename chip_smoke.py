"""Chip smoke: serve phi3-mini-3.8b at its published widths on one TPU v5e.

    python chip_smoke.py                 # one chip: dense + fused_int8 phases
    python chip_smoke.py --mesh 2x2      # four chips: the sharded serve path

The model is ``configs/phi3_mini_3_8b.py`` with nothing cut (32 layers,
d_model 3072, 32 heads of 96, d_ff 8192, vocab 32064), random bf16 weights
from ``--seed``.  Eight greedy requests stream through ``AsyncServer`` over
``ContinuousBatchingEngine(n_slots=4, max_len=1536)`` — W8A8 int8 decode
weights, the int8 KV slot pool, atomic prefill — and every stream must end
with its full budget and no error.  Outputs are checked against the
fixed-batch ``Engine`` on the same prompts (see ``LOGIT_BOUND``).

Phases (default): ``serve`` (dense backend), ``reference`` (fixed-batch
``Engine``), ``float`` (unquantized bf16 decode: the scale the bound is
read against), ``kernels`` (``Runtime(backend="fused_int8")``: the
Mosaic-compiled Pallas ``int8_matmul`` and ``decode_attention``, never the
interpreter), then the same requests served on the fused backend.  With
``--mesh DxM`` only the sharded path runs: the same requests served through
``launch.serve.make_serve_runtime`` (slot pool over ``data``, FFN/attention
over ``model``), compared with the one-chip engine on device 0.

Printed figures are smoke figures (one cold run, compiles included), not
benchmark numbers.  The last line of stdout is the JSON contract line; a
failed check exits non-zero before it, and so does a run without a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "phi3-mini-3.8b"
N_SLOTS, MAX_LEN = 4, 1536
# 64-1024-token prompts in four lengths (multiples of the engine's 16-token
# prefill bucket, so each length is one prefill compile shared by every
# engine); 32-128-token budgets drawn from --seed
PROMPT_LENS = (1024, 64, 512, 256, 1024, 64, 512, 256)
BUDGETS = (32, 128)
# Logit agreement bound.  First-step logits of this random-weight model
# have std ~1 (unit-RMS hidden state times an N(0, 1/d_model) head).  Two
# executions of the same W8A8 math (batch 4 vs 1, Pallas vs XLA, sharded vs
# one chip) differ by bf16/f32 reassociation, and where that flips an int8
# activation rounding (one quantum, 1/127 of the row's max) the flip
# carries through the remaining layers.  On a TPU v5e the fused kernels
# moved these logits by up to 0.26, while quantizing the model at all (W8A8
# vs bf16, the ``float`` phase) moved them by 0.35.  Half a logit std sits
# above both and below what a wrong result (a mis-mapped head, a lost
# scale, a mask off the cache) does to logits of unit spread.  Argmax must
# agree unless the reference's own gap to the emitted token is under the
# bound (a near-tie).
LOGIT_BOUND = 0.5


def fail(msg: str) -> None:
    raise SystemExit(f"chip smoke FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts only its read), and the cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.secs, self.hits = 0.0, 0

        def on_duration(event, secs, **_):
            if event in self.EVENTS:
                self.secs += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Phases:
    def __init__(self, device):
        self.device, self.clock = device, CompileClock()
        self.t_start = time.perf_counter()

    def run(self, name: str, fn, *args):
        c0, h0 = self.clock.secs, self.clock.hits
        t0 = time.perf_counter()
        out = fn(*args)
        stats = self.device.memory_stats() or {}
        print(f"smoke phase {name}: compile_s={self.clock.secs - c0:.2f} "
              f"cache_hits={self.clock.hits - h0} "
              f"wall_s={time.perf_counter() - t0:.2f} "
              f"peak_bytes={stats.get('peak_bytes_in_use', 'n/a')}",
              flush=True)
        return out


def make_traffic(cfg, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in PROMPT_LENS]
    budgets = [int(b) for b in rng.integers(BUDGETS[0], BUDGETS[1] + 1,
                                            len(PROMPT_LENS))]
    return prompts, budgets


def serve(cfg, params, rt, prompts, budgets):
    """The user path: AsyncServer over the continuous-batching engine, all
    requests live at once; returns each stream's tokens."""
    from repro.serve.engine import ContinuousBatchingEngine
    from repro.serve.server import AsyncServer, collect

    eng = ContinuousBatchingEngine(cfg, params, n_slots=N_SLOTS,
                                   max_len=MAX_LEN, rt=rt)

    async def run():
        async with AsyncServer(eng) as srv:
            streams = [await srv.submit(p, b) for p, b in zip(prompts,
                                                              budgets)]
            outs = await asyncio.gather(*(collect(s) for s in streams))
        return [(s.error, s.cancelled) for s in streams], outs

    ends, outs = asyncio.run(run())
    for i, ((error, cancelled), out) in enumerate(zip(ends, outs)):
        check(error is None, f"request {i} failed: {error}")
        check(not cancelled and len(out) == budgets[i],
              f"request {i} ended after {len(out)} of {budgets[i]} tokens")
    print(f"smoke serve: {len(outs)} streams, {sum(map(len, outs))} tokens, "
          f"{eng.stats['steps']} engine steps")
    return outs


def reference(cfg, params, rt, prompts, budgets, greedy_streams=True,
              quantize=True):
    """Fixed-batch ``Engine`` per prompt: the prefill logits, the first
    decode step's logits (W8A8 unless ``quantize=False``; fed the prefill
    argmax), and its greedy stream over the same budget."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.engine import Engine

    eng = Engine(cfg=cfg, params=params, rt=rt, max_len=MAX_LEN,
                 quantize=quantize)
    out = []
    for p, b in zip(prompts, budgets):
        batch = {"inputs": jnp.asarray([p], jnp.int32),
                 "lengths": jnp.asarray([len(p)], jnp.int32)}
        l0, state = eng._prefill(eng.params, batch)
        t0 = jnp.argmax(l0, -1).astype(jnp.int32)
        l1, _ = eng._decode(eng.qparams, state, t0)
        logits = np.stack([np.asarray(l0[0], np.float32),
                           np.asarray(l1[0], np.float32)])
        check(logits.shape == (2, cfg.vocab_size), f"logits {logits.shape}")
        check(bool(np.isfinite(logits).all()), "non-finite logits")
        toks = (np.asarray(eng.generate(batch, steps=b)[0][0]).tolist()
                if greedy_streams else None)
        out.append((logits, toks))
    return out


def gap(ref, tok: int) -> float:
    """How far the emitted token sits below the reference's best logit."""
    return float(ref.max() - ref[tok])


def prefix(a, b) -> int:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


def check_streams(name, outs, refs):
    """Each stream's first two tokens against the reference logits: the
    argmax, or a near-tie within LOGIT_BOUND; then the agreeing greedy
    prefix with the reference stream (reported, not required — random bf16
    weights give near-ties that fork later tokens)."""
    gaps, prefixes = [], []
    for i, (out, (logits, ref_toks)) in enumerate(zip(outs, refs)):
        steps = 2 if out[0] == int(logits[0].argmax()) else 1
        for s in range(steps):
            g = gap(logits[s], out[s])
            gaps.append(g)
            check(g <= LOGIT_BOUND, f"{name} request {i} token {s}: "
                  f"{out[s]} sits {g:.4f} below the reference argmax")
        if ref_toks is not None:
            prefixes.append(prefix(out, ref_toks))
    print(f"smoke {name}: max gap to reference argmax {max(gaps):.4f} "
          f"(bound {LOGIT_BOUND}); agreeing greedy prefix per request "
          f"{prefixes or 'n/a'}")


def check_logits(name, got, want):
    """First-step logits of two executions of the same model, per request:
    prefill and first decode step within LOGIT_BOUND elementwise."""
    worst = 0.0
    for i, ((a, _), (b, _)) in enumerate(zip(got, want)):
        for s in range(2):
            d = float(abs(a[s] - b[s]).max())
            worst = max(worst, d)
            check(d <= LOGIT_BOUND,
                  f"{name} request {i} step {s}: max |dlogit| {d:.4f}")
            ia, ib = int(a[s].argmax()), int(b[s].argmax())
            check(ia == ib or gap(b[s], ia) < LOGIT_BOUND,
                  f"{name} request {i} step {s}: argmax {ia} vs {ib}")
    print(f"smoke {name}: max |dlogit| {worst:.4f} (bound {LOGIT_BOUND})")


def init_params(cfg, seed: int, shardings=None):
    """Random bf16 weights built inside one jitted program (the stacked
    layers never exist twice)."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    init = jax.jit(lambda k: M.init_params(k, cfg, jnp.bfloat16),
                   out_shardings=shardings)
    return jax.block_until_ready(init(jax.random.key(seed)))


def one_chip(cfg, args, ph: Phases):
    from repro.kernels import resolve_interpret
    from repro.models.transformer import Runtime

    prompts, budgets = make_traffic(cfg, args.seed)
    params = ph.run("init", init_params, cfg, args.seed)
    outs = ph.run("serve", serve, cfg, params, Runtime(), prompts, budgets)
    gc.collect()
    refs = ph.run("reference", reference, cfg, params, Runtime(), prompts,
                  budgets)
    check_streams("serve vs reference", outs, refs)
    gc.collect()
    frefs = ph.run("float", reference, cfg, params, Runtime(), prompts,
                   budgets, False, False)
    quant_err = max(float(abs(a[1] - b[1]).max())
                    for (a, _), (b, _) in zip(refs, frefs))
    print(f"smoke W8A8 vs bf16 decode (the scale of LOGIT_BOUND): "
          f"max |dlogit| {quant_err:.4f}")

    check(not resolve_interpret(None), "Pallas kernels would be interpreted")
    fused = Runtime(backend="fused_int8")
    krefs = ph.run("kernels", reference, cfg, params, fused, prompts, budgets,
                   False)
    check_logits("fused_int8 vs dense", krefs, refs)
    gc.collect()
    kouts = ph.run("serve_fused", serve, cfg, params, fused, prompts, budgets)
    check_streams("fused serve vs fused reference", kouts, krefs)
    print(f"smoke fused vs dense serve: agreeing greedy prefix per request "
          f"{[prefix(a, b) for a, b in zip(kouts, outs)]}")
    return sum(map(len, outs)) + sum(map(len, kouts))


def meshed(cfg, args, ph: Phases):
    import jax
    import jax.numpy as jnp
    from repro.dist import sharding as SH
    from repro.launch.serve import make_serve_runtime
    from repro.models import model as M
    from repro.models.transformer import Runtime

    prompts, budgets = make_traffic(cfg, args.seed)
    rt = make_serve_runtime(args.mesh)
    psh = SH.param_shardings(cfg, M.abstract_params(cfg, jnp.bfloat16),
                             rt.mesh)
    params = ph.run("init", init_params, cfg, args.seed, psh)
    outs = ph.run("serve_mesh", serve, cfg, params, rt, prompts, budgets)
    gc.collect()
    mrefs = ph.run("reference_mesh", reference, cfg, params, rt, prompts,
                   budgets, False)
    gc.collect()
    params = jax.device_put(params, jax.devices()[0])
    refs = ph.run("reference_chip0", reference, cfg, params, Runtime(),
                  prompts, budgets)
    check_logits(f"mesh {args.mesh} vs one chip", mrefs, refs)
    check_streams(f"mesh {args.mesh} serve vs one-chip reference", outs,
                  refs)
    return sum(map(len, outs))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help='serve over a (data, model) mesh, e.g. "2x2"; runs '
                         "only the sharded path and its one-chip comparison")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    from repro.configs import registry
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    n = len(jax.devices())
    print(f"smoke device: {dev.device_kind} x{n} ({dev.platform}); "
          f"compile cache {cache}", flush=True)
    cfg = registry.get(ARCH)
    ph = Phases(dev)
    tokens = (meshed if args.mesh else one_chip)(cfg, args, ph)
    stats = dev.memory_stats() or {}
    print(f"smoke total: {tokens} streamed tokens, "
          f"wall_s={time.perf_counter() - ph.t_start:.2f}, "
          f"compile_s={ph.clock.secs:.2f}, cache_hits={ph.clock.hits}, "
          f"peak_bytes={stats.get('peak_bytes_in_use', 'n/a')} "
          f"(smoke figures, not benchmark numbers)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))


if __name__ == "__main__":
    main()
