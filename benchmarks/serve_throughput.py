"""Continuous-batching serve benchmark: Poisson arrivals, ragged prompts,
per-policy latency breakdown.

Drives the slot-scheduled engine with a synthetic open-loop trace (requests
arrive at Poisson times, with random prompt lengths and token budgets) and
reports, per scheduling policy: decode throughput, request latency, TTFT,
TPOT, queue delay (admit - arrival) percentiles, preemption count, and the
largest number of prefill tokens any single engine iteration absorbed
(``max_pf/step``) — the stall metric.  With ``--chunk`` the engine runs
chunked prefill, so ``max_pf/step`` is bounded by the iteration token
budget instead of the longest prompt: no decode iteration ever stalls
behind a full-prompt prefill.

With ``--spec-k 0,2,4,8`` every policy is additionally swept through the
speculative decode lane (draft k tokens, one batched verify step per
iteration): each record reports the draft acceptance rate and the TPOT
speedup relative to that policy's non-speculative (k=0) run — the paper's
per-token weight-read amortization, measured end to end.

With ``--spec-tree 0,4`` the sweep adds the tree-draft lane (a token
*tree* of N nodes per slot, ancestor-masked verify, accepted root-path
compacted in place): a tree record's ``speedup`` column is relative to
the non-speculative baseline like every other record, and its ``vs-lin``
column is the TPOT speedup over the linear ``spec_k`` record with the
same draft budget — equal budget, tree vs chain.

With ``--multi-step 1,2,4`` the sweep also covers the fused multi-step
decode lane (m greedy iterations per jitted call, argmax fed back on
device): the speedup column for an ``m>1`` record is relative to the same
policy's (k=0, m=1) baseline.  Every record carries the per-iteration
host-clock breakdown (``host_ms``: the engine's own host work, dispatch
included; ``dispatch_ms``: enqueueing jitted programs; ``wait_ms``: blocked
on device results) and the per-decode-step host transfer volume
(``xfer_bytes``) — the transfer-discipline trajectory (O(slots*m) greedy,
O(slots*k) sampled).  Device time itself comes from a profiler trace.

``--serve`` swaps the in-process replay for the *live* async front-end:
per-request coroutines sleep until their Poisson arrival and submit to a
running ``AsyncServer`` while the step loop executes in its worker thread
— the measured path includes the real admission handoff and stream pumps.
``--parity`` instead runs the closed-loop check: the streamed output must
be token-identical to ``generate_all`` on an identically-configured
engine for every policy (with ``--chunk``/``--spec-k`` honoured).  All
timing in every mode rides the engine's monotonic clock.

Run:  PYTHONPATH=src python benchmarks/serve_throughput.py \
          [--arch llama3-8b] [--requests 24] [--rate 20] [--slots 4] \
          [--policies fifo,sjf,priority,fair] [--chunk 8] \
          [--max-step-tokens 12] [--spec-k 0,2,4,8] [--drafter ngram] \
          [--multi-step 1,4] [--mesh 2x4] \
          [--json BENCH_serve_throughput.json]

``--json`` writes the summary record CI uploads as a workflow artifact
(the ``BENCH_*.json`` perf trajectory): one record per policy under
``"policies"`` plus the trace parameters at the top level.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time

import jax
import numpy as np

from repro.configs import registry
from repro.models import model as M
from repro.serve.engine import ContinuousBatchingEngine

try:                                   # invoked as benchmarks/<script>.py
    from common import reset_engine_stats
except ImportError:                    # imported as a benchmarks.* module
    from benchmarks.common import reset_engine_stats


def build_trace(rng, n, rate, max_prompt, max_new, n_users=4):
    """Poisson process: exponential inter-arrival gaps at ``rate`` req/s.
    Requests carry a priority class (0-3) and a user id so the priority and
    fair-share policies actually have something to reorder/preempt on."""
    gaps = rng.exponential(1.0 / rate, size=n)
    arrivals = np.cumsum(gaps)
    prompts = [rng.integers(0, 2**30, size=rng.integers(4, max_prompt + 1))
               for _ in range(n)]
    budgets = rng.integers(max(1, max_new // 2), max_new + 1, size=n)
    priorities = rng.integers(0, 4, size=n)
    users = [f"u{u}" for u in rng.integers(0, n_users, size=n)]
    return arrivals, prompts, budgets, priorities, users


def _cell(fmt, v):
    """One table cell; None (e.g. no acceptance data, no speedup baseline)
    prints as '-' at the column's width."""
    if v is not None:
        return fmt % v
    width = "".join(ch for ch in fmt[1:].split(".")[0] if ch.isdigit())
    dash = "-"
    return dash.ljust(int(width)) if fmt.startswith("%-") \
        else dash.rjust(int(width or 1))


def percentile(sorted_vals, q):
    if not sorted_vals:
        return float("nan")
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def make_chaos(args):
    """Fresh injector per engine (injectors carry fired-event state).
    Chaos never runs under --parity: the streamed/batch runs take different
    step counts, so step-indexed faults would hit different work."""
    if getattr(args, "parity", False):
        return None
    ber = getattr(args, "ber", None)
    every = getattr(args, "fault_every", 0)
    if ber is None and not every:
        return None
    from repro.serve.faults import FaultInjector
    return FaultInjector(seed=getattr(args, "faults_seed", 0),
                         ber=ber, step_fail_every=every)


def make_engine(cfg, params, args, rt, spec_k=0, multi_step=1,
                prefix_cache=None, spec_tree=0):
    max_len = args.max_prompt + args.max_new + 1
    if prefix_cache is None:
        prefix_cache = getattr(args, "prefix_cache", False)
    return ContinuousBatchingEngine(
        cfg, params, n_slots=args.slots, max_len=max_len, rt=rt,
        policy=args.policy, chunk=args.chunk,
        max_step_tokens=args.max_step_tokens,
        spec_k=spec_k, spec_tree=spec_tree,
        spec_branch=getattr(args, "spec_branch", 2),
        drafter=args.drafter, multi_step=multi_step,
        prefix_cache=prefix_cache,
        prefix_cache_rows=getattr(args, "prefix_rows", None),
        kv_swap=getattr(args, "kv_swap", False),
        faults=make_chaos(args))


def warm_engine(eng, args):
    """Warm the compile caches (budget 2 so the batched decode step compiles
    too, not just prefill) so the measured run is steady-state serving.
    Unchunked: one prompt per reachable prefill bucket; chunked: full and
    ragged chunks plus finalize."""
    if eng.chunk:
        warm_lens = sorted({min(args.max_prompt, eng.chunk),
                            min(args.max_prompt, eng.chunk + 1)})
    else:
        b = eng.prefill_bucket
        warm_lens = sorted({min(n, args.max_prompt)
                            for n in range(b, args.max_prompt + b, b)})
    warm = [list(range(1, max(2, n + 1))) for n in warm_lens]
    # multi-step engines warm with >= m budget so the fused block (and its
    # overshoot rewind) compiles before the measured run
    eng.generate_all(warm, [max(2, eng.multi_step)] * len(warm))
    # flush the warmup prompts' leaves and zero the counters: the measured
    # run starts from an empty trie with every slot back on the free heap
    reset_engine_stats(eng)


def replay_trace(eng, arrivals, prompts, budgets, priorities, users):
    """Open-loop replay: submit at trace time, step until drained.

    Time is read from the engine's own monotonic clock (``eng.now()``,
    re-zeroed here) — arrivals, admissions and the wall measurement share
    one timebase, so queue-delay/TTFT deltas cannot be skewed by mixing
    clocks (or by NTP stepping a wall clock mid-run)."""
    reqs = []
    eng.reset_clock()
    next_i = 0
    while next_i < len(prompts) or eng.scheduler.has_work():
        now = eng.now()
        while next_i < len(prompts) and arrivals[next_i] <= now:
            reqs.append(eng.submit(prompts[next_i], int(budgets[next_i]),
                                   arrival_time=float(arrivals[next_i]),
                                   priority=int(priorities[next_i]),
                                   user=users[next_i]))
            next_i += 1
        if not eng.step() and next_i < len(prompts):
            # idle: nothing resident yet, next arrival still in the future
            time.sleep(min(0.001, max(0.0, arrivals[next_i] - now)))
    wall = eng.now()
    return reqs, wall


def serve_trace(eng, args, arrivals, prompts, budgets, priorities, users):
    """Open-loop driver against the *live* async server: one coroutine per
    request sleeps until its Poisson arrival, submits to the running
    :class:`AsyncServer`, and consumes its token stream.  Unlike
    :func:`replay_trace` the step loop never sees the trace — admission
    happens while it runs, exactly like a real front-end.  Arrivals are
    stamped on the engine clock (single timebase; see ``eng.now()``)."""
    from repro.serve.server import AsyncServer, collect

    reqs = []

    async def run():
        eng.reset_clock()
        async with AsyncServer(eng, stream_buffer=args.stream_buffer) as srv:
            async def one(i):
                delay = arrivals[i] - eng.now()
                if delay > 0:
                    await asyncio.sleep(delay)
                stream = await srv.submit(
                    prompts[i], int(budgets[i]),
                    arrival_time=float(arrivals[i]),
                    priority=int(priorities[i]), user=users[i])
                reqs.append(stream.request)
                await collect(stream)

            await asyncio.gather(*(one(i) for i in range(len(prompts))))
            return eng.now()

    wall = asyncio.run(run())
    return reqs, wall


def run_parity(cfg, params, args, rt):
    """Closed-loop parity: the streamed path must be token-identical to
    ``generate_all`` on an identically-configured engine, per policy.
    Proves the async front-end (pending handoff, pump scheduling,
    bounded-queue backpressure) never perturbs what the engine emits."""
    from repro.serve.server import AsyncServer, collect

    rng = np.random.default_rng(args.seed)
    if getattr(args, "prefix_cache", False):
        # shared-prefix prompts so the warm path has something to hit:
        # the parity bar is warm-hit streams == a *cold* engine's
        # generate_all, token for token
        shared = rng.integers(0, cfg.vocab_size,
                              max(2, args.max_prompt // 2)).tolist()
        prompts = [shared + rng.integers(
                       0, cfg.vocab_size,
                       rng.integers(2, max(3, args.max_prompt
                                           - len(shared) + 1))).tolist()
                   for _ in range(args.requests)]
    else:
        prompts = [rng.integers(0, cfg.vocab_size,
                                rng.integers(4, args.max_prompt + 1)).tolist()
                   for _ in range(args.requests)]
    budgets = [int(rng.integers(max(1, args.max_new // 2),
                                args.max_new + 1))
               for _ in range(args.requests)]
    spec_k = max(int(s) for s in args.spec_k.split(","))
    spec_tree = max(int(s) for s in args.spec_tree.split(","))
    policies = (["fifo", "sjf", "priority:preempt",
                 f"fair:{max(1, args.max_new // 2)}"]
                if args.policies == "all" else args.policies.split(","))

    async def stream_all(eng):
        async with AsyncServer(eng, stream_buffer=args.stream_buffer) as srv:
            streams = [await srv.submit(p, b)
                       for p, b in zip(prompts, budgets)]
            return [list(o) for o in
                    await asyncio.gather(*(collect(s) for s in streams))]

    for pol in policies:
        args.policy = pol
        # the reference is always a cache-LESS engine: with --prefix-cache
        # the check below is literally "warm-hit streams == cold prefill"
        ref = make_engine(cfg, params, args, rt, spec_k=spec_k,
                          prefix_cache=False).generate_all(prompts, budgets)
        eng = make_engine(cfg, params, args, rt, spec_k=spec_k)
        got = asyncio.run(stream_all(eng))
        assert got == ref, (pol, got, ref)
        extra = ""
        if eng._pcache is not None:
            # second pass over the now-populated trie: warm admissions
            # must stream the exact tokens the cold reference produced
            got2 = asyncio.run(stream_all(eng))
            assert got2 == ref, (pol, "warm pass diverged", got2, ref)
            hits = eng.stats["prefix_hits"]
            assert hits > 0, (pol, "prefix cache never hit", eng._pcache.stats)
            extra = (f" prefix_hits={hits} "
                     f"saved={eng.stats['prefill_tokens_saved']}")
        print(f"PARITY_OK {pol} chunk={args.chunk} spec_k={eng.spec_k} "
              f"({sum(len(o) for o in got)} tokens){extra}")
        if spec_tree > 0:
            # tree lane parity against the same cache-less reference: the
            # ancestor-masked verify + path compaction must stream the
            # exact tokens the plain (and linear-spec) engines produced
            teng = make_engine(cfg, params, args, rt, spec_tree=spec_tree)
            tgot = asyncio.run(stream_all(teng))
            assert tgot == ref, (pol, "tree lane diverged", tgot, ref)
            print(f"PARITY_OK {pol} chunk={args.chunk} "
                  f"spec_tree={teng.spec_tree} branch={teng.spec_branch} "
                  f"({sum(len(o) for o in tgot)} tokens)")


def summarize(policy, eng, reqs, wall):
    # a request whose admission raised finishes with .error set and no
    # timing marks — keep it out of the percentiles, report the count
    failed = [r for r in reqs if r.error is not None]
    done = [r for r in reqs if r.error is None]
    gen = sum(len(r.output) for r in done)
    lat = sorted(r.finish_time - r.arrival_time for r in done)
    ttft = sorted(r.first_token_time - r.arrival_time for r in done)
    qdelay = sorted(r.admit_time - r.arrival_time for r in done)
    tpot = sorted((r.finish_time - r.first_token_time) / (len(r.output) - 1)
                  for r in done if len(r.output) > 1)
    rec = {
        "policy": policy,
        "failed": len(failed),
        "wall_s": wall, "generated_tokens": gen,
        "throughput_tok_s": gen / wall,
        "latency_p50_ms": percentile(lat, 0.50) * 1e3,
        "latency_p99_ms": percentile(lat, 0.99) * 1e3,
        "ttft_p50_ms": percentile(ttft, 0.50) * 1e3,
        "ttft_p99_ms": percentile(ttft, 0.99) * 1e3,
        "tpot_p50_ms": percentile(tpot, 0.50) * 1e3,
        "tpot_p99_ms": percentile(tpot, 0.99) * 1e3,
        "queue_delay_p50_ms": percentile(qdelay, 0.50) * 1e3,
        "queue_delay_p99_ms": percentile(qdelay, 0.99) * 1e3,
        "preemptions": eng.stats["preemptions"],
        "steps": eng.stats["steps"],
        "max_step_prefill_tokens": eng.stats["max_step_prefill_tokens"],
        # eng.spec_k, not the requested value: the engine zeroes it for
        # SSM stacks (no rewindable state) and never builds a drafter
        "spec_k": eng.spec_k,
        "spec_tree": eng.spec_tree,
        "spec_branch": eng.spec_branch if eng.spec_tree else None,
        "drafter": (eng._drafter.name
                    if eng.spec_k or eng.spec_tree else None),
        "verify_steps": eng.stats["verify_steps"],
        # None (JSON null), never NaN, when nothing was drafted
        "acceptance_rate": (eng.acceptance_rate
                            if eng.stats["spec_drafted"] else None),
        # per-window accepted-length histogram (index = drafted tokens
        # committed by one verify pass); null when no spec lane ran
        "spec_accept_hist": eng.stats.get("spec_accept_hist"),
        # eng.multi_step (like eng.spec_k): 1 for SSM stacks
        "multi_step": eng.multi_step,
        "multi_blocks": eng.stats["multi_blocks"],
        # per-iteration host-clock breakdown (engine host work, of it
        # dispatch; blocked on the device) + per-decode-step host transfer
        # volume — the device-resident-lane trajectory metrics
        "host_ms": 1e3 * (eng.stats["step_s"] - eng.stats["wait_s"])
        / max(1, eng.stats["steps"]),
        "dispatch_ms": 1e3 * eng.stats["dispatch_s"]
        / max(1, eng.stats["steps"]),
        "wait_ms": 1e3 * eng.stats["wait_s"] / max(1, eng.stats["steps"]),
        "xfer_bytes": eng.stats["decode_xfer_bytes"]
        / max(1, eng.stats["decode_steps"]),
        "xfer_bytes_total": eng.stats["xfer_bytes"],
    }
    if eng._pcache is not None:
        # present only when the cache is on — absent, not null, when off,
        # so downstream record schemas stay backward-compatible
        rec.update({
            "prefix_hits": eng.stats["prefix_hits"],
            "prefill_tokens_saved": eng.stats["prefill_tokens_saved"],
            "prefix_cached_rows": eng.stats["cached_tokens"],
            "prefix_aliases": eng._pcache.stats["aliases"],
            "prefix_evictions": eng._pcache.stats["evictions"]
            + eng._pcache.stats["reclaims"],
        })
    if eng._faults_on:
        # present only in chaos runs (absent, not null, otherwise)
        rec.update({
            "ecc_checks": eng.stats.get("ecc_checks", 0),
            "ecc_cycles": eng.stats.get("ecc_cycles", 0),
            "ecc_corrected_bits": eng.stats.get("ecc_corrected_bits", 0),
            "bitflips_injected": eng.stats.get("bitflips_injected", 0),
            "uncorrectable_blocks": eng.stats.get("uncorrectable_blocks", 0),
            "cold_rereads": eng.stats.get("cold_rereads", 0),
            "recovery_recomputes": eng.stats.get("recovery_recomputes", 0),
            "step_failures": eng.stats["step_failures"],
            "step_retries": eng.stats["step_retries"],
            "pool_rebuilds": eng.stats["pool_rebuilds"],
        })
    return rec


COLS = [("policy", "%-16s"), ("spec_k", "%6d"), ("spec_tree", "%5d"),
        ("multi_step", "%5d"),
        ("throughput_tok_s", "%8.1f"),
        ("ttft_p50_ms", "%9.1f"), ("ttft_p99_ms", "%9.1f"),
        ("tpot_p50_ms", "%9.2f"), ("tpot_p99_ms", "%9.2f"),
        ("latency_p99_ms", "%9.1f"), ("queue_delay_p50_ms", "%9.1f"),
        ("queue_delay_p99_ms", "%9.1f"), ("preemptions", "%5d"),
        ("max_step_prefill_tokens", "%11d"),
        ("host_ms", "%8.2f"), ("dispatch_ms", "%8.2f"), ("wait_ms", "%8.2f"),
        ("xfer_bytes", "%7.0f"),
        ("acceptance_rate", "%7.2f"), ("tpot_speedup", "%8.2f"),
        ("tpot_speedup_vs_linear", "%8.2f")]
HEAD = ("policy            spec_k   tree  mstep     tok/s  ttft-p50  "
        "ttft-p99  tpot-p50  tpot-p99   lat-p99  qdel-p50  qdel-p99  prmpt  "
        "max_pf/step   host_ms  disp_ms  wait_ms  xfer_B   accept  speedup   "
        "vs-lin")
# appended only when --prefix-cache is on (fields are absent otherwise)
PREFIX_COLS = [("prefix_hits", "%6d"), ("prefill_tokens_saved", "%8d")]
PREFIX_HEAD = "  pfhits   pfsaved"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean arrival rate, requests/second")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policies", default="fifo",
                    help='comma list of policies (or "all"), e.g. '
                         '"fifo,sjf,priority,fair:8"')
    ap.add_argument("--chunk", type=int, default=None,
                    help="chunked prefill size (None = atomic prefills)")
    ap.add_argument("--max-step-tokens", type=int, default=None,
                    help="per-iteration token budget (default slots + chunk)")
    ap.add_argument("--spec-k", default="0", metavar="K[,K...]",
                    help="speculative decode draft lengths to sweep, e.g. "
                         '"0,2,4,8" (0 = the non-speculative baseline the '
                         "TPOT speedup column is relative to)")
    ap.add_argument("--spec-tree", default="0", metavar="N[,N...]",
                    help="tree-draft node budgets to sweep, e.g. \"0,4\" "
                         "(0 = off).  A tree record's vs-lin column is its "
                         "TPOT speedup over the linear spec_k record with "
                         "the same draft budget — equal budget, tree vs "
                         "chain")
    ap.add_argument("--spec-branch", type=int, default=2,
                    help="tree-draft branching factor (with --spec-tree)")
    ap.add_argument("--drafter", default="ngram",
                    help="draft proposer: ngram[:N] | mtp")
    ap.add_argument("--multi-step", default="1", metavar="M[,M...]",
                    help="fused multi-step decode block sizes to sweep at "
                         'k=0, e.g. "1,2,4" (1 = the per-token baseline)')
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache (needs --chunk): adds "
                         "prefix_hits / prefill_tokens_saved to the table "
                         "and JSON; under --parity the streamed engine runs "
                         "a second warm pass that must match the cold "
                         "reference token for token")
    ap.add_argument("--prefix-rows", type=int, default=None,
                    help="prefix-cache row budget (default slots * max_len)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help='serve over a (data, model) mesh, e.g. "2x4"')
    ap.add_argument("--serve", action="store_true",
                    help="drive the live async server (open loop): per-"
                         "request coroutines sleep to their Poisson arrival, "
                         "submit to the running AsyncServer and consume the "
                         "token stream; same summary fields")
    ap.add_argument("--parity", action="store_true",
                    help="closed-loop check instead of a benchmark: streamed "
                         "output must be token-identical to generate_all per "
                         "policy (honours --chunk/--spec-k), then exit")
    ap.add_argument("--stream-buffer", type=int, default=16,
                    help="per-stream token queue bound in --serve/--parity")
    ap.add_argument("--kv-swap", action="store_true",
                    help="tiered KV pool (cold-store swaps); required for "
                         "--ber chaos to have a surface to corrupt")
    ap.add_argument("--ber", type=float, default=None,
                    help="chaos: inject NAND bit-flips into cold-store reads "
                         "at this raw bit error rate (needs --kv-swap)")
    ap.add_argument("--fault-every", type=int, default=0, metavar="N",
                    help="chaos: fail the jitted step every N engine steps "
                         "(consumes the donated pool; the engine's bounded "
                         "retry + pool rebuild path absorbs it)")
    ap.add_argument("--faults-seed", type=int, default=0,
                    help="chaos injector seed (fresh injector per engine)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the summary record as JSON")
    args = ap.parse_args()

    from repro.launch.serve import make_serve_runtime
    cfg = registry.get(args.arch).reduced()
    params = M.init_params(jax.random.key(0), cfg)
    rt = make_serve_runtime(args.mesh)

    if args.parity:
        run_parity(cfg, params, args, rt)
        return

    rng = np.random.default_rng(args.seed)
    arrivals, prompts, budgets, priorities, users = build_trace(
        rng, args.requests, args.rate, args.max_prompt, args.max_new)
    prompts = [(p % cfg.vocab_size).tolist() for p in prompts]

    # "all" exercises the preemptive variants with a quantum the trace's
    # token budgets can actually reach
    policies = (["fifo", "sjf", "priority:preempt",
                 f"fair:{max(1, args.max_new // 2)}"]
                if args.policies == "all" else args.policies.split(","))
    print(f"arch={cfg.name} slots={args.slots} requests={args.requests} "
          f"rate={args.rate}/s prompts 4..{args.max_prompt} "
          f"new {max(1, args.max_new//2)}..{args.max_new} "
          f"chunk={args.chunk} budget={args.max_step_tokens}")
    spec_ks = [int(s) for s in args.spec_k.split(",")]
    spec_trees = [int(s) for s in args.spec_tree.split(",")]
    multi_ms = [int(s) for s in args.multi_step.split(",")]
    # the lanes don't combine (spec_tree > spec_k > multi_step precedence
    # in the engine), so sweep each against the shared (k=0, m=1, tree=0)
    # baseline; a requested m=1 baseline is kept even when --spec-k omits 0
    combos = [(K, 1, 0) for K in spec_ks]
    for m in multi_ms:
        if (0, m, 0) not in combos:
            combos.append((0, m, 0))
    for n in spec_trees:
        if n and (0, 1, n) not in combos:
            combos.append((0, 1, n))
    cols = COLS + (PREFIX_COLS if args.prefix_cache else [])
    print(HEAD + (PREFIX_HEAD if args.prefix_cache else ""))
    records = {}
    for pol in policies:
        args.policy = pol
        recs = []
        for K, m, n in combos:
            eng = make_engine(cfg, params, args, rt, spec_k=K, multi_step=m,
                              spec_tree=n)
            warm_engine(eng, args)
            if args.serve:
                reqs, wall = serve_trace(eng, args, arrivals, prompts,
                                         budgets, priorities, users)
            else:
                reqs, wall = replay_trace(eng, arrivals, prompts, budgets,
                                          priorities, users)
            recs.append(summarize(pol, eng, reqs, wall))
        # speedup baseline: the (k=0, m=1) record wherever it sits in the
        # sweep (None — JSON null — when there is no baseline or NaN TPOTs)
        base = next((r for r in recs
                     if r["spec_k"] == 0 and r["multi_step"] == 1
                     and r["spec_tree"] == 0), None)
        base_tpot = base["tpot_p50_ms"] if base else None
        if base_tpot is None or base_tpot != base_tpot:
            base_tpot = None
        # per-budget linear-spec TPOTs: a tree record's vs-lin column is
        # its speedup over the chain window with the same draft budget
        lin_tpot = {r["spec_k"]: r["tpot_p50_ms"] for r in recs
                    if r["spec_k"] and r["multi_step"] == 1
                    and r["spec_tree"] == 0}
        for rec in recs:
            tpot = rec["tpot_p50_ms"]
            rec["tpot_speedup"] = (base_tpot / tpot
                                   if base_tpot and tpot == tpot else None)
            lin = lin_tpot.get(rec["spec_tree"]) if rec["spec_tree"] else None
            rec["tpot_speedup_vs_linear"] = (
                lin / tpot if lin and lin == lin and tpot == tpot else None)
            K, m, n = rec["spec_k"], rec["multi_step"], rec["spec_tree"]
            key = pol if (K == 0 and m == 1 and n == 0) else (
                f"{pol}@spec{K}" if K else
                f"{pol}@tree{n}" if n else f"{pol}@m{m}")
            records[key] = rec
            print("  ".join(_cell(fmt, rec[k]) for k, fmt in cols))

    if args.json:
        out = {"bench": "serve_throughput", "arch": cfg.name,
               "mode": "serve-open-loop" if args.serve else "replay",
               "slots": args.slots, "requests": args.requests,
               "rate_req_s": args.rate, "mesh": args.mesh,
               "seed": args.seed, "chunk": args.chunk,
               "max_step_tokens": args.max_step_tokens,
               "spec_k": spec_ks, "spec_tree": spec_trees,
               "spec_branch": args.spec_branch, "drafter": args.drafter,
               "multi_step": multi_ms,
               "prefix_cache": args.prefix_cache,
               "chaos": ({"ber": args.ber, "fault_every": args.fault_every,
                          "seed": args.faults_seed, "kv_swap": args.kv_swap}
                         if args.ber is not None or args.fault_every
                         else None),
               "policies": records}
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote", args.json)


if __name__ == "__main__":
    main()
