"""Serving engines: the paper's offload pipeline as a runnable system.

`prefill` is the "GPU stage" (full-precision summarization); its K/V land
quantized in the int8 SLC cache; `decode` loops the W8A8 PIM path.

Two engines share that pipeline:

* ``Engine`` — the paper's single-batch setting: one fixed batch of
  same-length prompts, prefill once, decode in lockstep.
* ``ContinuousBatchingEngine`` — the serving system: a request queue +
  slot scheduler admits variable-length prompts, packs active requests
  into decode slots (rows of the pooled SLC cache at heterogeneous
  positions), retires finished sequences, and backfills freed slots
  mid-flight.  The jitted decode step always sees a fixed [n_slots]
  batch, so continuous batching costs zero recompiles.

With ``chunk=c`` the continuous engine runs *chunked prefill*: admission no
longer stalls the decode pool for a full-prompt prefill — each iteration
packs the resident decode slots plus at most ``max_step_tokens - n_decoding``
prefill tokens (in ``[1, c]`` chunks at the request's ``prefill_pos`` cursor)
into one engine step, so TPOT of running requests never absorbs a whole
prompt.  Admission order and preemption are delegated to a pluggable
``SchedulingPolicy`` (FIFO / priority / SJF / fair-share).

The steady-state decode loop is *device-resident and transfer-minimal*:
every jitted serve step donates its decode-state argument, so the
``[layers, n_slots, S, H, D]`` int8 SLC pool (and the chunked-prefill
carry) update in place instead of being copied per token; greedy tokens
are argmax'd on device and only ``[n_slots]`` (or ``[n_slots, m]``) int32
vectors cross the host boundary; sampled slots get a device-side top-k
pre-select (``[n_slots, k]`` values+indices instead of full-vocab rows,
bit-identical streams).  With ``multi_step=m`` the engine *fuses* ``m``
greedy decode iterations into one jitted scan whenever the pool is in
pure decode steady state (no queue, no prefill, no replay, all greedy),
paying one host round-trip per ``m`` tokens; EOS/budget overshoot unwinds
through the same cursor rewind the speculative lane uses.

With ``spec_tree=n`` the continuous engine adds a *speculative decode
lane*: a drafter proposes a token tree of ``n`` nodes per decoding slot
(``spec_branch`` controls its branching; ``spec_branch=1`` is a plain
chain), and one batched verify step scores the root (the last committed
token) and all ``n`` nodes against the pooled SLC cache.  The window
carries per-row depths and int32 ancestor bitmasks, so the causal mask is
an ancestor mask; the host walks the verified tree for the longest
accepted root-path, ``tree_commit`` compacts that path's scattered K/V
rows into contiguous committed rows, and the cursor lands past them.
Rejected branches die in place (SLC writes are in place — rollback is
free, no erase).  When every slot's accepted path is its chain prefix
(always so for a chain) the gather would be an identity, so the commit is
the bare cursor rewind.  On the paper's bandwidth-bound PIM array every
decode step pays a full weight-read MVM pass, so verifying ``n+1`` tokens
per pass amortizes that read cost by the acceptance rate.  Greedy
speculative output is token-identical to the plain engine (chain-prefix
verify logits are bit-identical to sequential decode), and sampled
requests stay stream-exact: one RNG draw per emitted token, acceptance =
"node token equals the sampled token".
"""
from __future__ import annotations

import dataclasses
import time
from types import SimpleNamespace
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig
from repro.core import kvcache as KV
from repro.models import model as M
from repro.models import transformer as T
from repro.models.transformer import Runtime
from repro.ft.failures import StragglerWatchdog
from repro.serve.drafter import (Drafter, chain_parents, make_drafter,
                                 tree_depths_ancestors)
from repro.serve.faults import (ColdBlockCorrupt, FaultInjector,
                                FaultTolerance, InjectedStepFailure,
                                PoolConsumedError)
from repro.serve.quantize import quantize_tree
from repro.serve.scheduler import (Request, RequestState, Scheduler,
                                   SchedulingPolicy)


class RequestFailedError(RuntimeError):
    """Raised by :meth:`ContinuousBatchingEngine.generate_all` when any
    request finished with ``.error`` set (failed admission/prefill): an
    empty output must not masquerade as a real empty generation.  The
    failed requests ride along in ``.failures``."""

    def __init__(self, failures: list[Request]):
        self.failures = failures
        super().__init__("; ".join(
            f"request {r.rid}: {r.error}" for r in failures))


# MoE counters a program returns (models.transformer.zero_counts) -> the
# engine stats they add to
_PREFILL_COUNTS = {"local_pairs": "moe_prefill_local_pairs"}
_DECODE_COUNTS = {"local_pairs": "moe_local_pairs",
                  "experts_touched": "moe_experts_touched"}


def _place_on_mesh(cfg: ModelConfig, params: Any, qparams: Any, rt: Runtime):
    """Land the float (prefill) and QLC (decode) param trees on ``rt.mesh``
    per ``dist.sharding``; returns (params, qparams, qparam_shardings)."""
    from repro.dist import sharding as SH
    mesh = rt.mesh
    params = jax.device_put(params, SH.param_shardings(
        cfg, jax.eval_shape(lambda: params), mesh))
    qsh = SH.param_shardings(cfg, jax.eval_shape(lambda: qparams), mesh,
                             serve=rt.serve_resident_moe)
    return params, jax.device_put(qparams, qsh), qsh


@dataclasses.dataclass
class Engine:
    cfg: ModelConfig
    params: Any                       # float params (prefill path)
    rt: Runtime = dataclasses.field(default_factory=Runtime)
    max_len: int = 256
    quantize: bool = True

    def __post_init__(self):
        self.qparams = quantize_tree(self.params) if self.quantize else self.params
        if self.rt.mesh is not None:
            self.params, self.qparams, _ = _place_on_mesh(
                self.cfg, self.params, self.qparams, self.rt)

        def prefill(p, b):
            return M.prefill(p, self.cfg, b, self.max_len, self.rt)

        def decode_step(p, s, t):
            return M.decode_step(p, self.cfg, s, t, self.rt)

        self._prefill = jax.jit(prefill)
        # the decode state is donated: each step's int8 SLC pool updates in
        # place instead of being copied per token (the caller reassigns)
        self._decode = jax.jit(decode_step, donate_argnums=(1,))

    def generate(self, batch: dict, steps: int, greedy: bool = True,
                 rng: jax.Array | None = None):
        """Prefill the prompt batch then generate ``steps`` tokens.
        Returns (tokens [B, steps], per-stage timings).  ``greedy=False``
        requires an explicit ``rng`` (e.g. ``jax.random.key(0)``)."""
        if not greedy and rng is None:
            raise ValueError(
                "generate(greedy=False) needs a sampling rng; passing none "
                "used to silently fall back to greedy argmax")
        t0 = time.perf_counter()
        logits, state = self._prefill(self.params, batch)
        logits = jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        # KV handoff complete: decode runs against the quantized weights
        toks = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        t0 = time.perf_counter()
        for i in range(steps):
            toks.append(tok)
            logits, state = self._decode(self.qparams, state, tok)
            if greedy:
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                rng, k = jax.random.split(rng)
                tok = jax.random.categorical(k, logits).astype(jnp.int32)
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0
        return (jnp.stack(toks, axis=1),
                {"prefill_s": t_prefill, "decode_s": t_decode,
                 "tpot_s": t_decode / max(1, steps)})


class ContinuousBatchingEngine:
    """Iteration-level scheduling over a fixed pool of decode slots.

    Each engine ``step()`` is one serving iteration:

      1. retire finished requests (slots freed for backfill);
      2. preempt residents the policy bumps back to the queue (only when
         the queue is blocked on slots) — recompute-style: output is kept
         and replayed through the decode path on re-admission, so a
         preempted request is token-identical to an un-preempted run;
      3. admit queued requests into free slots in **policy** order
         (FIFO / priority / SJF / fair-share);
      4. advance in-flight prefills.  Unchunked (``chunk=None``): each
         admission runs one atomic single-request prefill (the "GPU
         stage") and lands its int8 KV row into the pooled decode state.
         Chunked (``chunk=c``): PREFILLING slots consume ``[1, c]`` token
         chunks at their ``prefill_pos`` cursor against a carried float
         K/V buffer, bounded by the per-iteration **token budget**
         (``max_step_tokens`` minus one per resident decode slot); the
         final chunk quantizes the carry into the slot row and emits the
         request's first token;
      5. one batched W8A8 decode step over all slots; slots with a
         DECODING resident emit their next token (greedy, or per-request
         temperature/top-k sampling), other slots compute into masked
         garbage.  With ``spec_tree=n`` this decode is a *speculative
         verify*: a drafter proposes an ``n``-node token tree per slot,
         the batched verify step scores all ``n+1`` window rows at once
         (their K/V appended in place at each slot's cursor), each slot
         commits its longest accepted root-path and the rest rolls back
         — up to ``n+1`` tokens per slot per weight-read pass.  A
         replaying (preempt-resumed) slot drafts its own recorded tokens
         as a chain, so replay consumes the spec lane at full acceptance
         and stays token-identical.  SSM/hybrid stacks keep the
         one-token decode (their recurrent state cannot rewind);
         ``spec_tree`` is ignored for them like ``chunk``.

    Chunked prefill is exact for attention stacks (the carry keeps prefill
    precision), so outputs are token-identical to the unchunked engine for
    every policy.  SSM/hybrid stacks keep the exact-length prefill path
    (their recurrent state would integrate chunk-boundary error): ``chunk``
    is ignored for them.  Unchunked attention prefills are bucketed
    (multiples of ``prefill_bucket``) — ragged right-padding is exact there
    thanks to per-request length masking in
    :func:`repro.models.transformer.prefill`.

    Passing a ``Runtime`` with a mesh turns on the sharded-serve path:
    params and quantized "QLC" weights land on the mesh per
    ``dist.sharding.param_shardings`` (experts resident per
    ``moe_serve_strategy`` when ``rt.serve_resident_moe``), and the pooled
    decode state — the slot-pool SLC cache — shards its slot axis over the
    data axes with KV heads over ``model``.  The jitted decode step pins
    those shardings so slot churn (``write_slot`` admissions) never
    migrates the pool, and the chunked-prefill carry is pinned the same
    way (``prefill_carry_shardings``).  Scheduling stays host-side and
    identical to the single-device engine, so outputs are token-for-token
    reproducible.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, n_slots: int = 4,
                 max_len: int = 256, quantize: bool = True,
                 rt: Runtime | None = None, prefill_bucket: int = 16,
                 policy: str | SchedulingPolicy | None = "fifo",
                 chunk: int | None = None,
                 max_step_tokens: int | None = None,
                 spec_tree: int = 0,
                 spec_branch: int = 2,
                 drafter: str | Drafter | None = "ngram",
                 multi_step: int = 1,
                 topk_preselect: bool = True,
                 prefix_cache: bool = False,
                 prefix_cache_rows: int | None = None,
                 kv_swap: bool = False,
                 cold_rows: int | None = None,
                 drain_stall_limit: int = 8,
                 faults: "FaultInjector | bool | None" = None,
                 max_step_retries: int = 3,
                 retry_backoff_s: float = 0.02,
                 watchdog_factor: float = 8.0):
        if cfg.family == "encdec":
            raise NotImplementedError(
                "continuous batching targets decoder-only LMs")
        self.cfg = cfg
        self.params = params
        self.rt = rt or Runtime()
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_bucket = prefill_bucket
        self.qparams = quantize_tree(params) if quantize else params
        self._has_ssm = any(cfg.layer_kind(i) == "ssm"
                            for i in range(cfg.n_layers))
        # one chip's MoE share counts its routing: the atomic prefill, the
        # decode step and the fused decode block return the counters, and
        # they ride the next fetch (engine._fetch)
        self._moe_counts = self.rt.mesh is None and any(
            cfg.is_moe_layer(i) for i in range(cfg.n_layers))
        self._pending_counts: list = []
        # SSM/hybrid stacks keep the exact-length prefill (recurrent-state
        # boundary); attention stacks chunk
        self.chunk = None if (chunk is None or self._has_ssm) else int(chunk)
        if self.chunk is not None and self.chunk < 1:
            raise ValueError("chunk must be >= 1")
        if spec_tree < 0:
            raise ValueError("spec_tree must be >= 0 (0 = no speculation)")
        if spec_tree > 30:
            # the ancestor bitmask is one int32 per window row: node w owns
            # bit w, the root owns bit 0, so spec_tree drafted nodes need
            # bits 1..spec_tree — bit 31 (the sign bit) stays unused
            raise ValueError("spec_tree must be <= 30 (int32 ancestor mask)")
        if spec_branch < 1:
            raise ValueError("spec_branch must be >= 1")
        # SSM/hybrid recurrent state cannot rewind: like `chunk`, the spec
        # lane silently falls back to the exact one-token decode there
        self.spec_tree = 0 if self._has_ssm else int(spec_tree)
        self.spec_branch = int(spec_branch)
        if multi_step < 1:
            raise ValueError("multi_step must be >= 1 (1 = per-token loop)")
        # fused multi-step decode also leans on the cursor rewind to unwind
        # EOS/budget overshoot, so SSM/hybrid stacks keep the 1-token loop
        self.multi_step = 1 if self._has_ssm else int(multi_step)
        self.topk_preselect = bool(topk_preselect)
        if self.chunk:
            self.max_step_tokens = (max_step_tokens if max_step_tokens
                                    else n_slots + self.chunk)
            if self.max_step_tokens < n_slots + 1:
                raise ValueError(
                    f"max_step_tokens {self.max_step_tokens} leaves no room "
                    f"for prefill progress beside {n_slots} decode slots "
                    f"(need >= n_slots + 1)")
        else:
            self.max_step_tokens = max_step_tokens
        self.scheduler = Scheduler(n_slots, max_len, policy)
        self.policy = self.scheduler.policy
        # prefix cache: radix-indexed KV reuse over the slot pool.  GQA
        # attention stacks only — the MLA pool caches the compressed
        # latent (no per-head K/V to seed the warm carry from) and SSM
        # state cannot restart mid-prompt — both silently fall back to
        # cold prefill, mirroring the `chunk`/`spec_tree` discipline.
        self._pcache = None
        if prefix_cache and not self._has_ssm and cfg.attn_type != "mla":
            if self.chunk is None:
                raise ValueError(
                    "prefix_cache needs chunked prefill (chunk=c): warm "
                    "admissions resume the chunked cursor mid-prompt")
            from repro.serve.prefix_cache import RadixPrefixCache
            budget = (prefix_cache_rows if prefix_cache_rows
                      else n_slots * max_len)
            self._pcache = RadixPrefixCache(budget)
            self.scheduler.attach_prefix_cache(self._pcache)
        # the pool keeps headroom rows past max_len so no lane's in-place
        # appends starting at the last live position ever clamp-wrap onto
        # valid rows — the audited rule lives in kvcache.pool_headroom
        self._state_len = max_len + KV.pool_headroom(
            spec_tree=self.spec_tree, multi_step=self.multi_step)
        self.state = M.init_decode_state(cfg, n_slots, self._state_len)
        if drain_stall_limit < 1:
            raise ValueError("drain_stall_limit must be >= 1")
        self.drain_stall_limit = int(drain_stall_limit)
        # tiered pool: hot slot rows stay in the donated int8 pool above;
        # the cold tier holds swapped-out preemption victims and demoted
        # prefix-cache leaves as quantized host-side blocks with metered
        # transfers (serve.kv_swap).  The crossover prices a victim's
        # replay against the modeled per-token decode cost so preemption
        # becomes a swap-vs-recompute policy choice.
        self._swap = None
        if kv_swap:
            from repro.serve.kv_swap import SwapManager
            replay_tpot = None
            try:
                from repro.core.mapping import flash_tpot_for
                replay_tpot = float(
                    flash_tpot_for(cfg, context_len=max_len)["total"])
            except Exception:
                pass  # unmapped config: no crossover, swap whenever room
            swap_budget = (cold_rows if cold_rows is not None
                           else n_slots * max_len)
            self._swap = SwapManager(
                swap_budget,
                jax.eval_shape(T.read_slot, self.state, jnp.int32(0)),
                replay_tpot_s=replay_tpot)
        # fault tolerance (DESIGN §1j): the injector is the chaos source
        # (faults=True turns on detection/metering with no injection), the
        # FaultTolerance layer owns cold-block checksums + the metered ECC
        # pipeline, and the retry/rebuild machinery lives in step().
        self._injector = faults if isinstance(faults, FaultInjector) else None
        self._faults_on = bool(faults)
        self._ft = None                   # built after the stats dict below
        if max_step_retries < 0:
            raise ValueError("max_step_retries must be >= 0")
        self.max_step_retries = int(max_step_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self._watchdog = StragglerWatchdog(factor=watchdog_factor)
        self._state_sharding = None       # set by _shard_over_mesh
        self._last_tok = np.zeros((n_slots,), np.int32)
        self._slot_pos = np.zeros((n_slots,), np.int64)   # host cursor mirror
        self._carries: dict[int, Any] = {}        # slot -> prefill carry
        self._rngs: dict[int, np.random.Generator] = {}   # rid -> sampler
        self._topk_fns: dict[int, Any] = {}       # k -> jitted lax.top_k
        self._io: dict[str, Any] | None = None    # mesh decode-I/O shardings
        self._next_rid = 0
        # cancellation inbox: `cancel()` only appends (GIL-atomic), so an
        # async server may call it from another thread while `step()` runs;
        # the step loop drains it at the next iteration boundary
        self._cancels: list[Request] = []
        self._t0 = time.monotonic()
        self.stats = {"steps": 0, "decode_steps": 0, "prefill_tokens": 0,
                      "chunks": 0, "max_step_prefill_tokens": 0,
                      "max_step_total_tokens": 0, "preemptions": 0,
                      "verify_steps": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "multi_blocks": 0,
                      "multi_tokens": 0, "xfer_bytes": 0,
                      "decode_xfer_bytes": 0, "step_s": 0.0,
                      # host seconds at the step's boundaries, each beside
                      # its profiler span: enqueueing jitted programs
                      # (engine.dispatch), blocked on device results
                      # (engine.fetch; decode_wait_s the decode lanes'
                      # share), admission work incl. swap restores
                      # (engine.prefill); prefills counts the prefills
                      # that reached their first token
                      "dispatch_s": 0.0, "wait_s": 0.0, "decode_wait_s": 0.0,
                      "prefill_s": 0.0, "prefills": 0,
                      # recovery machinery is always armed (a donated step
                      # can genuinely fail with no injector), so these
                      # counters always exist
                      "timeouts": 0, "slow_steps": 0, "step_failures": 0,
                      "step_retries": 0, "pool_rebuilds": 0}
        if self._moe_counts:
            # absent-when-off, like the prefix/swap keys: routed pairs that
            # landed on the experts held here (prefill; decode) and, per
            # decode step and MoE layer, the held experts that got a token
            self.stats.update({"moe_prefill_local_pairs": 0,
                               "moe_local_pairs": 0,
                               "moe_experts_touched": 0})
        if self._pcache is not None:
            # keys exist only when the cache is on so downstream record
            # schemas stay backward-compatible (absent, not null, when off)
            self.stats.update({"prefix_hits": 0, "cached_tokens": 0,
                               "prefill_tokens_saved": 0})
        if self._swap is not None:
            # same absent-when-off rule as the prefix-cache keys
            self.stats.update({"swap_outs": 0, "swap_ins": 0,
                               "swap_out_bytes": 0, "swap_in_bytes": 0,
                               "swap_out_cycles": 0, "swap_in_cycles": 0,
                               "preempt_swaps": 0, "preempt_recomputes": 0})
        if self._faults_on:
            # absent-when-off, like the prefix/swap keys: the FT layer's
            # ECC metering and recovery-path counters
            self.stats.update({"ecc_checks": 0, "ecc_pages": 0,
                               "ecc_cycles": 0, "ecc_corrected_bits": 0,
                               "bitflips_injected": 0,
                               "uncorrectable_blocks": 0, "cold_rereads": 0,
                               "recovery_recomputes": 0, "slot_losses": 0,
                               "quarantined_slots": 0})
            self._ft = FaultTolerance(self.stats, self._injector)
            if self._swap is not None:
                self._swap.attach_faults(self._ft)
        if self._pcache is not None and self._swap is not None:
            # LRU pressure demotes prefix leaves to the cold tier instead
            # of dropping them; store evictions relay back as drop_cold
            self._pcache.attach_cold_tier(self._demote_leaf_rows,
                                          self._swap.drop)
        if self.spec_tree:
            # per-window accepted-length histogram: index = drafted tokens
            # committed by one verify pass (0 .. draft budget), list-valued
            # so it rides the same stats dict as the scalar counters
            self.stats["spec_accept_hist"] = [0] * (self.spec_tree + 1)

        # every serve-path step donates its decode-state / carry argument:
        # the [layers, n_slots, S, H, D] int8 K/V pool (and the chunked
        # prefill's float carry) update in place instead of being copied
        # per call.  Each call site reassigns the engine's reference, so
        # the donated (deleted) buffer is never touched again.
        fn = self._step_fns()
        self._prefill = jax.jit(fn.prefill)
        if self.chunk:
            # a fresh carry per admission: donation consumes the previous
            # one, so a shared zero template would die on first use
            self._carry_init = jax.jit(fn.init_prefill_carry)
            self._chunk_fn = jax.jit(fn.prefill_chunk, donate_argnums=(1,))
            self._finalize_write = jax.jit(fn.finalize_write,
                                           donate_argnums=(0,))
        if self._pcache is not None:
            # warm admission pair: the row gather copies the matched leaf's
            # rows into the new slot (donated pool, in-place), and the warm
            # carry dequantizes those rows into the float chunk carry so
            # prefill resumes at the cached cursor.  The carry read is NOT
            # donated — the pool stays live for the step's other slots.
            self._gather = jax.jit(T.copy_slot_prefix, donate_argnums=(0,))
            self._warm_carry = jax.jit(fn.warm_prefill_carry)
        if self.spec_tree:
            self._drafter = make_drafter(drafter, cfg, self.rt,
                                         self.spec_tree, self.spec_branch)
            self._h_last = (np.zeros((n_slots, cfg.d_model), np.float32)
                            if self._drafter.kind == "model" else None)
            self._verify_tree = jax.jit(fn.verify_tree, donate_argnums=(1,))
            self._tree_commit = jax.jit(M.tree_commit, donate_argnums=(0,))
        if self.multi_step > 1:
            self._multi = jax.jit(fn.multi_decode_step, donate_argnums=(1,))
        if self.rt.mesh is None:
            self._decode = jax.jit(fn.decode_step, donate_argnums=(1,))
            self._write = jax.jit(T.write_slot, donate_argnums=(0,))
            if self._swap is not None:
                self._read_slot = jax.jit(T.read_slot)
        else:
            self._shard_over_mesh(fn)

    def _step_fns(self) -> SimpleNamespace:
        """The serve path's jitted step functions, each a named ``def`` so
        that its compiled module, and the device trace, read
        ``jit_<name>``.  The slot row moves (``T.write_slot``,
        ``T.read_slot``, ``T.copy_slot_prefix``) and ``M.tree_commit`` are
        jitted under their own names."""
        cfg, rt, max_len = self.cfg, self.rt, self.max_len
        counts = self._moe_counts

        def prefill(p, b):
            return M.prefill(p, cfg, b, max_len, rt, counts=counts)

        def init_prefill_carry():
            return M.init_prefill_carry(cfg, max_len + self.chunk)

        def prefill_chunk(p, c, t, n):
            return M.prefill_chunk(p, cfg, c, t, n, rt)

        def finalize_write(s, slot, c):
            return T.write_slot(s, slot,
                                M.finalize_prefill_carry(cfg, c, max_len))

        def warm_prefill_carry(s, slot, n):
            return M.warm_prefill_carry(cfg, s, slot, n,
                                        max_len + self.chunk)

        def decode_step(p, s, t):
            return M.decode_step(p, cfg, s, t, rt, counts=counts)

        def multi_decode_step(p, s, t):
            return M.multi_decode_step(p, cfg, s, t, self.multi_step, rt,
                                       counts=counts)

        def verify_tree(p, s, t, dep, a):
            return M.verify_step(p, cfg, s, t, rt, depth=dep, anc=a)

        return SimpleNamespace(**{f.__name__: f for f in (
            prefill, init_prefill_carry, prefill_chunk, finalize_write,
            warm_prefill_carry, decode_step, multi_decode_step, verify_tree)})

    # -- sharded-serve path -----------------------------------------------
    def _shard_over_mesh(self, fn: SimpleNamespace) -> None:
        """Place params, QLC weights and the slot pool on ``rt.mesh`` and
        pin every serve step's in/out shardings to the pool layout.

        The pins serve double duty: slot churn (``write_slot`` admissions)
        never migrates the pool, and — because XLA only aliases a donated
        input whose layout equals the output's — identical in/out shardings
        are what lets ``donate_argnums`` keep the SLC pool updating in
        place on the mesh too (``dist.sharding.serve_step_shardings``)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist import sharding as SH
        cfg, mesh = self.cfg, self.rt.mesh
        self.params, self.qparams, qsh = _place_on_mesh(
            cfg, self.params, self.qparams, self.rt)
        pool_shape = ShapeConfig("serve", self._state_len, self.n_slots,
                                 "decode")
        ssh = SH.decode_state_shardings(
            cfg, pool_shape, jax.eval_shape(lambda: self.state), mesh)
        self.state = jax.device_put(self.state, ssh)
        self._state_sharding = ssh        # pool rebuild re-lands here
        self._io = SH.serve_step_shardings(self.n_slots, mesh)
        self._io["pos"] = NamedSharding(mesh, P())
        if self._swap is not None:
            # swap I/O pins beside the pool: the row lift reads the sharded
            # pool but lands replicated batch=1 rows (host-bound anyway),
            # and swap-in pushes land replicated before the pinned write
            rsh = SH.swap_row_shardings(mesh)
            self._read_slot = jax.jit(
                T.read_slot, in_shardings=(ssh, rsh["slot"]),
                out_shardings=rsh["row"])
            self._io["swap_row"] = rsh["row"]
        self._decode = jax.jit(
            fn.decode_step,
            in_shardings=(qsh, ssh, self._io["tokens"]),
            out_shardings=(self._io["logits"], ssh), donate_argnums=(1,))
        if self.multi_step > 1:
            self._multi = jax.jit(
                fn.multi_decode_step,
                in_shardings=(qsh, ssh, self._io["tokens"]),
                out_shardings=(self._io["block"], ssh), donate_argnums=(1,))
        if self.spec_tree:
            # the verify step's I/O pins beside the pool so the spec lane
            # never migrates the SLC rows (same rule as the decode step);
            # the [B, T] depth/anc window operands shard their slot axis
            # beside the draft tokens; the commit scalars replicate (they
            # feed per-slot dynamic slicing inside the jitted path gather)
            vsh = SH.verify_shardings(self.n_slots, mesh)
            self._io["verify_tokens"] = vsh["tokens"]
            self._io["tree_window"] = vsh["window"]
            self._io["tree_commit"] = vsh["commit"]
            self._verify_tree = jax.jit(
                fn.verify_tree,
                in_shardings=(qsh, ssh, vsh["tokens"], vsh["window"],
                              vsh["window"]),
                out_shardings=(vsh["logits"], vsh["hidden"], ssh),
                donate_argnums=(1,))
            self._tree_commit = jax.jit(
                M.tree_commit,
                in_shardings=(ssh,) + (vsh["commit"],) * 4,
                out_shardings=ssh, donate_argnums=(0,))
        # admissions write a replicated B=1 row into the sharded pool; the
        # out_shardings pin keeps the pool resident (no migration per admit)
        self._write = jax.jit(T.write_slot, out_shardings=ssh,
                              donate_argnums=(0,))
        if self.chunk:
            csh = SH.prefill_carry_shardings(
                cfg, jax.eval_shape(self._carry_init), mesh)
            self._carry_init = jax.jit(fn.init_prefill_carry,
                                       out_shardings=csh)
            # pin the carry's layout across chunk steps (heads stay over
            # `model`, matching the pool so finalize->write never reshards;
            # matching in/out is also the donation-alias condition)
            self._chunk_fn = jax.jit(
                fn.prefill_chunk,
                out_shardings=(NamedSharding(mesh, P()), csh),
                donate_argnums=(1,))
            self._finalize_write = jax.jit(
                fn.finalize_write, out_shardings=ssh, donate_argnums=(0,))
        if self._pcache is not None:
            # the gather is pinned beside the pool: in/out = the pool's
            # shardings (the donation-alias condition) with replicated
            # scalar operands, so a warm admission never migrates a slot
            # row and meshed serve stays token-identical to single-device
            gsh = SH.prefix_gather_shardings(mesh)
            self._gather = jax.jit(
                T.copy_slot_prefix,
                in_shardings=(ssh, gsh["slot"], gsh["slot"], gsh["rows"]),
                out_shardings=ssh, donate_argnums=(0,))
            self._warm_carry = jax.jit(
                fn.warm_prefill_carry,
                in_shardings=(ssh, gsh["slot"], gsh["rows"]),
                out_shardings=csh)

    # -- request intake ---------------------------------------------------
    def submit(self, prompt: Iterable[int], max_new_tokens: int,
               eos_id: int | None = None,
               arrival_time: float | None = None, *,
               priority: int = 0, user: str | None = None,
               temperature: float = 0.0, top_k: int | None = None,
               seed: int | None = None,
               deadline_s: float | None = None) -> Request:
        if temperature < 0:
            raise ValueError("temperature must be >= 0 (0 = greedy)")
        if top_k is not None and top_k < 1:
            raise ValueError("top_k must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be > 0 when set")
        req = Request(rid=self._next_rid, prompt=list(map(int, prompt)),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      arrival_time=(self._now() if arrival_time is None
                                    else arrival_time),
                      priority=priority, user=user, temperature=temperature,
                      top_k=top_k, seed=seed, deadline_s=deadline_s)
        self._next_rid += 1
        self.scheduler.submit(req)
        return req

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def now(self) -> float:
        """Engine timebase: seconds since construction / :meth:`reset_clock`.

        Every request timestamp (``arrival_time`` default, ``admit_time``,
        ``first_token_time``, ``finish_time``) is stamped from this clock,
        and it is **monotonic** (``time.monotonic``): queue-delay/TTFT
        deltas can never go negative under NTP/wall-clock skew.  Open-loop
        drivers that inject ``arrival_time`` should stamp arrivals from
        this same clock (or a fixed offset of it) so the timebase stays
        single-sourced."""
        return self._now()

    def reset_clock(self) -> None:
        """Re-zero the engine clock (e.g. after compile warm-up) so request
        timestamps share the caller's timebase."""
        self._t0 = time.monotonic()

    # -- host<->device transfer discipline --------------------------------
    # Every steady-state transfer goes through these two helpers: transfers
    # are *explicit* (jax.device_get / jax.device_put, so serving survives
    # a `jax.transfer_guard("disallow")` scope) and metered — `xfer_bytes`
    # counts everything, `decode_xfer_bytes` only the decode lane, which
    # the transfer-discipline regression test pins to O(n_slots * m) for
    # greedy and O(n_slots * k) for sampled decode.
    #
    # Spans (`jax.profiler.TraceAnnotation`) sit at the step's boundaries
    # and record only while a profiler runs, on the device trace's clock:
    # engine.step, .schedule, .prefill, .push, .dispatch, .fetch, .emit.
    # Names stay bare (per-event values go in keyword arguments) so a trace
    # reduction can group by name.
    def _fetch(self, x, decode: bool = False):
        """Explicit device->host fetch (counted): the host blocks until the
        device has produced ``x`` and copied it back (``wait_s``).  MoE
        counters of programs dispatched since the last fetch come back in
        the same transfer and add to ``stats``."""
        pending, self._pending_counts = self._pending_counts, []
        t0 = time.perf_counter()
        with TraceAnnotation("engine.fetch"):
            out = jax.device_get((x, pending) if pending else x)
        dt = time.perf_counter() - t0
        if pending:
            out, got = out
            for counts in got:
                for name, v in counts.items():
                    self.stats[name] += int(v)
        self.stats["wait_s"] += dt
        n = sum(a.nbytes for a in jax.tree.leaves(out))
        self.stats["xfer_bytes"] += n
        if decode:
            self.stats["decode_wait_s"] += dt
            self.stats["decode_xfer_bytes"] += n
        return out

    def _push(self, arr: np.ndarray, sharding=None, decode: bool = False):
        """Explicit host->device transfer (counted)."""
        self.stats["xfer_bytes"] += arr.nbytes
        if decode:
            self.stats["decode_xfer_bytes"] += arr.nbytes
        with TraceAnnotation("engine.push"):
            if sharding is not None:
                return jax.device_put(arr, sharding)
            return jax.device_put(arr)

    def _counted(self, out, stats: dict[str, str]):
        """A MoE program's outputs less its counters, which wait for the
        next fetch and then add to ``self.stats``, each counter under the
        name ``stats`` gives it."""
        if not self._moe_counts:
            return out
        *out, counts = out
        self._pending_counts.append(
            {name: counts[key] for key, name in stats.items()})
        return tuple(out)

    def _dev(self, fn, *args):
        """Enqueue a jitted program (``dispatch_s``); returns before the
        device has run it."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.dispatch"):
            out = fn(*args)
        self.stats["dispatch_s"] += time.perf_counter() - t0
        return out

    def _device_topk(self, logits, k: int):
        """jitted ``lax.top_k`` over the vocab axis (cached per k): the
        sampled decode path's pre-select, shipping [B, k] values+indices
        to the host sampler instead of full-vocab rows.  XLA's top_k
        breaks ties in favour of lower indices — the same total order as
        the host's stable sort — so pre-selected sampling stays
        bit-identical to the full-vocab path."""
        fn = self._topk_fns.get(k)
        if fn is None:
            def topk(lg):
                return jax.lax.top_k(lg, k)

            fn = self._topk_fns[k] = jax.jit(topk)
        return self._dev(fn, logits)

    # -- per-request sampling ---------------------------------------------
    def _rng_for(self, req: Request) -> np.random.Generator:
        rng = self._rngs.get(req.rid)
        if rng is None:
            seed = req.seed if req.seed is not None else req.rid
            rng = self._rngs[req.rid] = np.random.default_rng(seed)
        return rng

    def _draw_from(self, req: Request, idx: np.ndarray,
                   logits: np.ndarray) -> int:
        """One cumulative draw over candidate ids ``idx`` (ascending) with
        aligned f64 temperature-scaled logits.  One uniform per token, so a
        preempted request's replay re-consumes the stream identically."""
        z = logits - logits.max()
        p = np.exp(z)
        p /= p.sum()
        u = self._rng_for(req).random()
        j = min(int(np.searchsorted(np.cumsum(p), u, side="right")),
                len(idx) - 1)
        return int(idx[j])

    def _sample_token(self, req: Request, row: np.ndarray) -> int:
        """Next token for one slot from a full-vocab logits row: greedy
        argmax at temperature 0, else top-k temperature sampling from a
        per-request deterministic stream (seeded by ``req.seed``, falling
        back to the rid)."""
        if req.temperature <= 0:
            return int(row.argmax())
        logits = row.astype(np.float64) / req.temperature
        if req.top_k is not None and req.top_k < logits.size:
            # exactly top_k candidates: a `logits >= kth` test admits every
            # token tied at the k-th logit (> top_k of them).  Selection is
            # O(V): argpartition pins the k-th largest value, every id
            # strictly above it is in, and the ids tied at it fill the tail
            # lowest-id-first — the same candidate set the old full-vocab
            # stable argsort picked, without the O(V log V) sort.
            k = req.top_k
            part = np.argpartition(-logits, k - 1)[:k]
            vth = logits[part].min()
            above = np.nonzero(logits > vth)[0]
            ties = np.nonzero(logits == vth)[0][:k - above.size]
            idx = np.sort(np.concatenate([above, ties]))
        else:
            idx = np.arange(logits.size)
        return self._draw_from(req, idx, logits[idx])

    def _sample_candidates(self, req: Request, vals: np.ndarray,
                           idx: np.ndarray) -> int:
        """:meth:`_sample_token` over device-pre-selected candidates:
        ``vals``/``idx`` are the row's top-k logits descending (ties lowest
        id first — `lax.top_k`'s order matches the stable sort), so the
        first ``req.top_k`` entries are exactly the full-vocab candidate
        set and the f64 softmax/cumsum pipeline below is bit-identical."""
        if req.temperature <= 0:
            return int(idx[0])                    # argmax == top-1
        k = len(idx) if req.top_k is None else min(req.top_k, len(idx))
        order = np.asarray(idx[:k])
        perm = np.argsort(order, kind="stable")   # ids back to ascending
        logits = vals[:k].astype(np.float64)[perm] / req.temperature
        return self._draw_from(req, order[perm], logits)

    def _next_tokens(self, logits, dec: list[tuple[int, Request]]) -> np.ndarray:
        """Next token per decoding slot from the device-resident [B, V]
        logits.  Greedy slots never see the logits (argmax on device, one
        int32 per slot crosses); sampled slots with bounded ``top_k`` get
        the device-side pre-select ([B, k] values+indices); only a sampled
        request with ``top_k=None`` (full-vocab sampling) falls back to
        shipping its whole row."""
        if all(req.temperature <= 0 for _, req in dec):
            return self._fetch(jnp.argmax(logits, -1).astype(jnp.int32),
                               decode=True)
        out = np.zeros((self.n_slots,), np.int64)
        ks = [req.top_k for _, req in dec if req.temperature > 0]
        # pre-select only for genuinely bounded top-k (k < V): at k >= V it
        # would sort and ship the whole vocab twice over
        if self.topk_preselect and all(
                k is not None and k < self.cfg.vocab_size for k in ks):
            kmax = max(ks)
            vals, idx = self._fetch(self._device_topk(logits, kmax),
                                    decode=True)
            with TraceAnnotation("engine.emit", slots=len(dec)):
                for slot, req in dec:
                    out[slot] = self._sample_candidates(req, vals[slot],
                                                        idx[slot])
            return out
        rows = self._fetch(logits, decode=True).astype(np.float32)
        with TraceAnnotation("engine.emit", slots=len(dec)):
            for slot, req in dec:
                out[slot] = self._sample_token(req, rows[slot])
        return out

    # -- admission: prefill into a slot -----------------------------------
    def _bucket(self, n: int) -> int:
        if self._has_ssm:
            return n                       # exact: no padding through SSM state
        b = self.prefill_bucket
        return min(self.max_len, -(-n // b) * b)

    def _first_token(self, req: Request, logits) -> int:
        """First token from the prefill logits ([1, V]): argmax stays on
        device for greedy, bounded sampling gets the top-k pre-select —
        the full row only crosses for unbounded (``top_k=None``) sampling."""
        if req.temperature <= 0:
            return int(self._fetch(jnp.argmax(logits, -1))[0])
        if (self.topk_preselect and req.top_k is not None
                and req.top_k < self.cfg.vocab_size):
            vals, idx = self._fetch(self._device_topk(logits, req.top_k))
            return self._sample_candidates(req, vals[0], idx[0])
        return self._sample_token(
            req, self._fetch(logits)[0].astype(np.float32))

    def _emit_first(self, req: Request, logits) -> None:
        """A request's prefill just completed: emit its first token (or
        re-feed the recorded one when resuming after preemption) and move
        it to DECODING."""
        # the draw always runs so a resumed request's sampling stream stays
        # aligned with its original run
        tok = self._first_token(req, logits)
        self.stats["prefills"] += 1
        if req.output:                     # resumed: recorded token wins
            tok = req.output[0]
            req.replay_pos = 1
        else:
            req.output.append(tok)
            req.replay_pos = len(req.output)
            req.first_token_time = self._now()
            self.policy.on_tokens(req, 1)
        req.state = RequestState.DECODING
        self._last_tok[req.slot] = tok
        # host mirror of the slot cursor (the spec lane's rollback base):
        # after prefill the cache holds exactly the prompt
        self._slot_pos[req.slot] = req.prompt_len
        if self.spec_tree and self._h_last is not None:
            self._h_last[req.slot] = 0.0      # MTP head free-runs post-prefill
        if req.replay_pos >= len(req.output) and req.should_stop():
            self._retire(req, self._now())            # budget of 1 token

    def _admit_atomic(self, req: Request) -> int:
        """Unchunked admission: one full-prompt prefill lands the int8 KV
        row.  Exception-safe: a failed prefill (OOM, compile error) frees
        the slot and fails the request instead of leaking the slot."""
        plen = req.prompt_len
        padded = self._bucket(plen)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :plen] = req.prompt
        batch = {"inputs": jnp.asarray(toks)}
        if padded != plen or not self._has_ssm:
            batch["lengths"] = jnp.array([plen], jnp.int32)
        try:
            logits, one = self._counted(
                self._dev(self._prefill, self.params, batch),
                _PREFILL_COUNTS)
            self.state = self._dev(self._write, self.state,
                                   jnp.int32(req.slot), one)
        except Exception as e:                        # noqa: BLE001
            self._fail(req, f"{type(e).__name__}: {e}")
            self._check_pool_alive(e)
            return 0
        req.prefill_pos = plen
        self._emit_first(req, logits)
        return plen

    def _admit_chunked(self, req: Request) -> None:
        """Chunked admission: allocate the request's float carry — cold
        (zeros, cursor 0) or, on a prefix-cache hit, warm.

        A warm admission walks the trie for the longest cached prefix of
        the prompt (capped at ``prompt_len - 1`` so at least one suffix
        token always runs through chunked prefill and emits the first
        token), gathers the matched rows into the request's slot (skipped
        when the scheduler aliased the admission onto the cached leaf's
        own slot — ``leaf_for`` resolves it), dequantizes them into the
        carry, and starts the cursor at the match — ``prefill_pos`` moves
        past the cached tokens without ever running them."""
        if self._pcache is None:
            self._carries[req.slot] = self._dev(self._carry_init)
            return
        src = n_hit = None
        leaf = self._pcache.leaf_for(req.slot)
        if leaf is not None:                  # aliased: rows already here
            src, n_hit = req.slot, leaf.n_rows
        elif req.adopted_rows >= 1:           # reclaim adopted the match's
            src, n_hit = req.slot, req.adopted_rows   # slot: rows in place
        else:
            hit, n = self._pcache.lookup(req.prompt, req.prompt_len - 1)
            if hit is not None and n >= 1:
                if hit.slot is None:      # cold leaf: promote via swap-in
                    n = self._promote_cold_hit(hit, req, n)
                    if n >= 1:
                        src, n_hit = req.slot, n
                else:
                    src, n_hit = hit.slot, n
        if src is None:
            self._carries[req.slot] = self._dev(self._carry_init)
            return
        if src != req.slot:
            self.state = self._dev(self._gather, self.state,
                                   jnp.int32(src), jnp.int32(req.slot),
                                   jnp.int32(n_hit))
        self._carries[req.slot] = self._dev(
            self._warm_carry, self.state, jnp.int32(req.slot),
            jnp.int32(n_hit))
        req.prefill_pos = n_hit
        self.stats["prefix_hits"] += 1
        self.stats["prefill_tokens_saved"] += n_hit
        self.stats["cached_tokens"] = self._pcache.cached_rows

    def _promote_cold_hit(self, leaf, req: Request, n: int) -> int:
        """A warm admission matched a demoted (cold) leaf: consume it, swap
        its block into the request's own slot, and resume chunked prefill
        at the match (no gather — the rows land where they're needed;
        retirement republishes the longer prefix hot).  Returns the usable
        row count, 0 on a vanished block (fall back to a cold start)."""
        key = self._pcache.promote(leaf)
        try:
            blob, rows, cost = self._swap.swap_in(key)
        except ColdBlockCorrupt:
            # tier-crossing detection: the demoted leaf rotted in the cold
            # store (uncorrectable bit-flips).  The block is already
            # dropped; a cold prefill recomputes the same rows exactly.
            return 0
        except KeyError:                  # pragma: no cover - guard
            return 0
        one = jax.tree.map(
            lambda a: self._push(np.asarray(a),
                                 self._io and self._io["swap_row"]),
            blob)
        self.state = self._dev(self._write, self.state,
                               jnp.int32(req.slot), one)
        self.stats["swap_ins"] += 1
        self.stats["swap_in_bytes"] += cost.n_bytes
        self.stats["swap_in_cycles"] += cost.cycles_in
        return min(n, rows)

    def _run_chunk(self, req: Request, n: int) -> int:
        """Advance one PREFILLING slot by ``n`` prompt tokens (one [1, chunk]
        call; the tail beyond ``n`` is padding).  Finalizes into the pool on
        the last chunk.  Exception-safe like :meth:`_admit_atomic`."""
        slot = req.slot
        toks = np.zeros((1, self.chunk), np.int32)
        toks[0, :n] = req.prompt[req.prefill_pos:req.prefill_pos + n]
        try:
            logits, self._carries[slot] = self._dev(
                self._chunk_fn, self.params, self._carries[slot],
                jnp.asarray(toks), jnp.int32(n))
            req.prefill_pos += n
            self.stats["chunks"] += 1
            if req.prefill_pos >= req.prompt_len:
                carry = self._carries.pop(slot)
                self.state = self._dev(self._finalize_write, self.state,
                                       jnp.int32(slot), carry)
                self._emit_first(req, logits)
        except Exception as e:                        # noqa: BLE001
            self._carries.pop(slot, None)
            self._fail(req, f"{type(e).__name__}: {e}")
            self._check_pool_alive(e)
            return 0
        return n

    def _check_pool_alive(self, cause: Exception) -> None:
        """Admission is exception-safe (one failed request, serving
        continues) *unless* the failing call had already consumed the
        donated pool state mid-execution — then the engine cannot serve
        the other residents and must fail loudly now, not with a confusing
        'Array has been deleted' on the next decode step.  Compile-time
        and pre-dispatch failures (the common cases) never consume the
        donated buffer, so they keep the per-request isolation."""
        if self._pool_consumed():
            raise PoolConsumedError(
                "the decode pool was consumed by a failed donated write; "
                "the engine cannot continue serving its residents"
            ) from cause

    def _pool_consumed(self) -> bool:
        return jax.tree.leaves(self.state)[0].is_deleted()

    def _bump(self, key: str, n: int = 1) -> None:
        """Increment a stats counter only when it exists (the recovery
        machinery is always armed; its FT-only counters are not)."""
        if key in self.stats:
            self.stats[key] += n

    def _preempt(self, req: Request, now: float) -> None:
        """Bump a resident back to the queue.  With the tiered pool on,
        preemption is a policy choice: a DECODING victim's committed rows
        swap out to the cold tier when the metered tier round-trip beats
        replaying its tokens (``SwapManager.prefer_swap``); otherwise —
        crossover says recompute, cold tier full, or mid-prefill victim —
        it falls back to the recompute path (re-prefill + replay)."""
        self._carries.pop(req.slot, None)
        swapped = 0
        if self._swap is not None and req.state is RequestState.DECODING:
            swapped = self._swap_out_victim(req)
        if swapped:
            self.stats["preempt_swaps"] += 1
            # the sampled stream continues where it left off (no replay
            # draws), so the per-request rng must survive the round trip
        else:
            if self._swap is not None:
                self.stats["preempt_recomputes"] += 1
            self._rngs.pop(req.rid, None)  # replay re-consumes the stream
        self.scheduler.preempt(req, now, swapped_rows=swapped)
        self.stats["preemptions"] += 1

    def _relay_cold_evictions(self, evicted: list) -> None:
        """Unpinned (prefix-leaf) blocks the cold store LRU-dropped to make
        room: tell the trie so the matching cold leaves die too."""
        if self._pcache is not None:
            for key in evicted:
                self._pcache.drop_cold(key)

    def _swap_out_victim(self, req: Request) -> int:
        """Lift the victim's committed rows off the pool and store them
        cold under ``("req", rid)`` (pinned: a preempted resident's rows
        are never LRU-dropped — only cancel/fail/swap-in release them).
        Returns the swapped row count, 0 on fallback-to-recompute."""
        n = int(self._slot_pos[req.slot])
        replay_tokens = req.prompt_len + len(req.output)
        if n < 1 or not self._swap.prefer_swap(n, replay_tokens):
            return 0
        one = self._fetch(self._dev(self._read_slot, self.state,
                                    jnp.int32(req.slot)))
        ok, evicted, cost = self._swap.swap_out(
            ("req", req.rid), one, n, pinned=True)
        self._relay_cold_evictions(evicted)
        if not ok:
            return 0
        self.stats["swap_outs"] += 1
        self.stats["swap_out_bytes"] += cost.n_bytes
        self.stats["swap_out_cycles"] += cost.cycles_out
        return n

    def _admit_swapped(self, req: Request) -> None:
        """Re-admission of a swap-preempted victim: swap its cold block in,
        land it in the assigned slot with the donating ``write_slot``, and
        resume DECODING — no prefill; replay only the tokens recorded after
        the block's committed rows (a fresh preemption block carries all of
        them, so the replay window is empty; a *stale* recovery copy — slot
        loss after more decode — re-feeds the tail).  Restored rows are
        byte-identical to the ones that left, so the continuation is
        token-identical to an unpreempted run.

        With the FT layer on, the read crosses the ECC + checksum pipeline;
        an uncorrectable block falls back to deterministic recompute-replay
        in this same admission (the request re-prefills from scratch and
        replays every recorded token — token-identical by the replay
        discipline).  Greedy requests keep the block in the store as a
        recovery copy (``keep=True``); sampled requests must not restore
        from a stale copy (tail replay would re-consume RNG draws the live
        stream already used), so they pop it like before.

        Returns True when the request was handled here (restored, or
        failed hard); False tells the caller to fall through to the
        normal recompute admission path."""
        n = req.swapped_rows
        req.swapped_rows = 0
        keep = self._ft is not None and req.temperature <= 0
        try:
            blob, rows, cost = self._swap.swap_in(("req", req.rid),
                                                  keep=keep)
        except (ColdBlockCorrupt, KeyError):
            # uncorrectable block, or an unpinned recovery copy the store
            # LRU-evicted after the scheduler elected a cold re-read —
            # both recoverable: fall back to recompute-replay
            self._bump("recovery_recomputes")
            self._rngs.pop(req.rid, None)  # replay re-consumes the stream
            req.prefill_pos = 0
            req.replay_pos = 0
            return False
        except Exception as e:                        # noqa: BLE001
            self._fail(req, f"{type(e).__name__}: {e}")
            return True
        try:
            one = jax.tree.map(
                lambda a: self._push(np.asarray(a),
                                     self._io and self._io["swap_row"]),
                blob)
            self.state = self._dev(self._write, self.state,
                                   jnp.int32(req.slot), one)
        except Exception as e:                        # noqa: BLE001
            self._fail(req, f"{type(e).__name__}: {e}")
            self._check_pool_alive(e)
            return True
        assert rows == n, f"cold block rows {rows} != ledger {n}"
        self.stats["swap_ins"] += 1
        self.stats["swap_in_bytes"] += cost.n_bytes
        self.stats["swap_in_cycles"] += cost.cycles_in
        fed = rows - req.prompt_len       # output tokens already in the rows
        assert 0 <= fed < len(req.output), \
            f"cold rows {rows} outside prompt {req.prompt_len} + " \
            f"output {len(req.output)}"
        req.prefill_pos = req.prompt_len
        req.replay_pos = fed + 1
        req.state = RequestState.DECODING
        self._last_tok[req.slot] = req.output[fed]
        self._slot_pos[req.slot] = rows
        if self.spec_tree and self._h_last is not None:
            self._h_last[req.slot] = 0.0  # MTP head free-runs post-restore
        return True

    def _demote_leaf_rows(self, slot: int, n_rows: int, key) -> bool:
        """Prefix-cache demotion hook: move an LRU-evicted leaf's rows to
        the cold tier (unpinned — the store may LRU-drop them later) so a
        future warm admission can promote instead of cold-prefilling."""
        one = self._fetch(self._dev(self._read_slot, self.state,
                                    jnp.int32(slot)))
        ok, evicted, cost = self._swap.swap_out(key, one, n_rows,
                                                pinned=False)
        self._relay_cold_evictions(evicted)
        if ok:
            self.stats["swap_outs"] += 1
            self.stats["swap_out_bytes"] += cost.n_bytes
            self.stats["swap_out_cycles"] += cost.cycles_out
        return ok

    def _retire(self, req: Request, now: float) -> None:
        publish = None
        if self._pcache is not None and req.slot is not None:
            # committed rows = the host cursor mirror (prompt + every fed
            # generated token), capped at max_len - 1 so a claimed row can
            # never collide with a clamped garbage append on an inactive
            # slot (appends clamp to >= state_len - T >= max_len - 1)
            publish = min(int(self._slot_pos[req.slot]), self.max_len - 1)
        self.scheduler.retire(req, now, publish_rows=publish)
        if self._swap is not None:
            # a retained recovery copy (FT keep-on-restore) dies with the
            # request; without one this is a no-op
            self._swap.drop(("req", req.rid))
        if self._pcache is not None:
            self.stats["cached_tokens"] = self._pcache.cached_rows
        self._rngs.pop(req.rid, None)     # release the per-request sampler

    def _fail(self, req: Request, error: str) -> None:
        if req.slot is not None:          # died mid-chunk: drop its carry
            self._carries.pop(req.slot, None)
        if self._swap is not None:        # orphaned cold block, if any
            self._swap.drop(("req", req.rid))
        self.scheduler.fail(req, self._now(), error=error)
        self._rngs.pop(req.rid, None)

    # -- cancellation ------------------------------------------------------
    def cancel(self, req: Request) -> None:
        """Request cancellation (client disconnect): takes effect at the
        next iteration boundary — the slot is freed mid-decode (or
        mid-chunked-prefill / between spec windows), partial output is
        kept, and the request ends CANCELLED.  Safe to call from another
        thread while ``step()`` is running (append-only inbox)."""
        self._cancels.append(req)

    def _apply_cancels(self, now: float) -> bool:
        """Drain the cancellation inbox.  Slot hygiene mirrors a failure:
        the in-flight prefill carry and the per-request sampler are
        dropped with the slot.  A cancelled DECODING resident's committed
        cursor is already what ``_slot_pos`` mirrors (every overshooting
        lane rewound before the step ended — the same rewind EOS overshoot
        uses), so freeing the slot needs no device work: the row is dead
        in place until the next admission overwrites it."""
        did = False
        while self._cancels:
            req = self._cancels.pop(0)
            if req.done:
                continue                  # raced with retire/fail: no-op
            if req.slot is not None:
                self._carries.pop(req.slot, None)
            if self._swap is not None:    # swapped-out victim cancelled
                self._swap.drop(("req", req.rid))
            self.scheduler.cancel(req, now)
            self._rngs.pop(req.rid, None)
            did = True
        return did

    # -- fault recovery (DESIGN §1j) ---------------------------------------
    def _apply_deadlines(self, now: float) -> None:
        """Terminal TIMEOUT for any request past its ``deadline_s`` budget
        (queued or resident) — slot/carry/cold-block hygiene mirrors a
        cancel, the partial output is kept."""
        for req in (list(self.scheduler.queue)
                    + list(self.scheduler.active.values())):
            if req.deadline_s is None or req.done:
                continue
            if now - req.arrival_time < req.deadline_s:
                continue
            if req.slot is not None:
                self._carries.pop(req.slot, None)
            if self._swap is not None:
                self._swap.drop(("req", req.rid))
            self.scheduler.timeout(req, now)
            self._rngs.pop(req.rid, None)
            self.stats["timeouts"] += 1

    def _recover_resident(self, req: Request, now: float) -> None:
        """Move a resident off a dead pool/slot while keeping its stream
        token-identical: a greedy resident with a retained cold copy
        re-enters the queue as a swap restore (possibly-stale rows + tail
        replay — greedy-only, a sampled tail replay would re-consume RNG
        draws the live stream already used); everything else
        recompute-replays from scratch."""
        self._carries.pop(req.slot, None)
        key = ("req", req.rid)
        if (self._swap is not None and req.temperature <= 0
                and req.output and self._swap.has(key)):
            rows = self._swap.store.rows_of(key)
            fed = rows - req.prompt_len
            if 0 <= fed < len(req.output):
                # the copy is load-bearing until re-admission: re-pin it so
                # an LRU pass can't evict it out from under the ledger
                self._swap.store.pin(key)
                self.scheduler.preempt(req, now, swapped_rows=rows)
                self._bump("cold_rereads")
                return
            self._swap.drop(key)          # ledger-inconsistent copy
        self._rngs.pop(req.rid, None)     # replay re-consumes the stream
        self.scheduler.preempt(req, now, swapped_rows=0)
        self._bump("recovery_recomputes")

    def _lose_slot(self, slot: int, now: float) -> None:
        """Whole plane/slot loss: recover the resident (cold re-read or
        recompute-replay), drop any cached leaf rows living there, and
        quarantine the slot for good.  Fatal only once no healthy slot
        remains (``Scheduler.quarantine_slot`` raises)."""
        if slot in self.scheduler.quarantined or not 0 <= slot < self.n_slots:
            return
        self._bump("slot_losses")
        req = self.scheduler.active.get(slot)
        if req is not None:
            self._recover_resident(req, now)
        if self._pcache is not None:
            self._pcache.drop_slot(slot)
        self.scheduler.quarantine_slot(slot)
        if "quarantined_slots" in self.stats:
            self.stats["quarantined_slots"] = len(self.scheduler.quarantined)

    def _rebuild_pool(self) -> None:
        """Rebuild the donated decode pool from committed host state after
        a failed donated step consumed it.  Every resident preempts off
        the dead pool (cold re-read when a recovery copy exists, else
        recompute-replay — token-identical either way), in-flight float
        carries are dropped (they died with the pool), hot prefix-cache
        leaves are dropped (their rows are gone; demoted *cold* leaves
        survive — they live host-side), and a fresh pool lands with the
        original shardings.  The slot ledger stays balanced: every slot
        ends either free or quarantined."""
        now = self._now()
        self.stats["pool_rebuilds"] += 1
        self._carries.clear()
        for slot, req in sorted(list(self.scheduler.active.items())):
            self._recover_resident(req, now)
        if self._pcache is not None:
            self._pcache.drop_hot()
        state = M.init_decode_state(self.cfg, self.n_slots, self._state_len)
        if self._state_sharding is not None:
            state = jax.device_put(state, self._state_sharding)
        self.state = state
        self._slot_pos[:] = 0
        self._last_tok[:] = 0
        if self.spec_tree and self._h_last is not None:
            self._h_last[:] = 0.0

    # -- one serving iteration --------------------------------------------
    def step(self) -> bool:
        """Run one engine iteration; returns True if any work was done.

        Transient device errors are survived here (DESIGN §1j): a step
        that consumed the donated pool (a failed donated call — injected
        or real) triggers bounded retry-with-backoff, each attempt first
        rebuilding a fresh pool from committed host state
        (:meth:`_rebuild_pool` — residents preempt to the cold tier or
        recompute-replay, so recovered streams stay token-identical).
        Anything else, and retry exhaustion, propagates.  A step-latency
        watchdog (``ft.failures.StragglerWatchdog``) flags straggling
        iterations in ``stats["slow_steps"]``."""
        t0 = time.perf_counter()
        try:
            with TraceAnnotation("engine.step", step=self.stats["steps"] + 1):
                attempt = 0
                while True:
                    try:
                        return self._step()
                    except Exception as e:            # noqa: BLE001
                        if not (isinstance(e, InjectedStepFailure)
                                or self._pool_consumed()):
                            raise
                        self.stats["step_failures"] += 1
                        if attempt >= self.max_step_retries:
                            raise RuntimeError(
                                f"engine step failed {attempt + 1} time(s); "
                                "retry budget exhausted") from e
                        if self.retry_backoff_s > 0:
                            time.sleep(self.retry_backoff_s
                                       * (2.0 ** attempt))
                        attempt += 1
                        self.stats["step_retries"] += 1
                        self._rebuild_pool()
        finally:
            dt = time.perf_counter() - t0
            self.stats["step_s"] += dt
            if self._watchdog.observe(self.stats["steps"], dt):
                self.stats["slow_steps"] += 1

    def _prefill_work(self, req: Request, tokens: int, work, *args) -> int:
        """``work(req, *args)``, one admission's prefill work, under the
        ``engine.prefill`` span; its host seconds add to ``prefill_s``."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.prefill", rid=req.rid, tokens=tokens):
            got = work(req, *args)
        self.stats["prefill_s"] += time.perf_counter() - t0
        return got

    def _admit(self, req: Request) -> int:
        """Admit one request the scheduler granted a slot; returns the
        prompt tokens prefilled now (chunked admission prefills later)."""
        if req.swapped_rows:
            # swap-preempted victim: restore its rows from the cold tier
            # and resume decoding — both engine flavours.  False = the
            # block was uncorrectably corrupt; fall through to the
            # recompute admission below (token-identical replay)
            if self._admit_swapped(req) or req.done:
                return 0
        if self.chunk:
            # exception-safe like _admit_atomic: a failed carry allocation
            # fails one request, never leaks the slot
            try:
                self._admit_chunked(req)
            except Exception as e:                    # noqa: BLE001
                self._fail(req, f"{type(e).__name__}: {e}")
                self._check_pool_alive(e)
            return 0
        return self._admit_atomic(req)

    def _step(self) -> bool:
        with TraceAnnotation("engine.schedule"):
            now = self._now()
            self.stats["steps"] += 1
            step_pf = 0
            cancelled = self._apply_cancels(now)
            for slot, req in list(self.scheduler.active.items()):
                if (req.state is RequestState.DECODING
                        and req.replay_pos >= len(req.output)
                        and req.should_stop()):
                    self._retire(req, now)
            self._apply_deadlines(now)
            if self._injector is not None:
                for slot in self._injector.lost_slots(self.stats["steps"]):
                    self._lose_slot(slot, now)
            # preemption: only meaningful when the queue is blocked on
            # slots — and a reclaimable prefix-cache leaf means it is not
            # blocked (admission evicts LRU cache rows before any resident
            # is bumped)
            if not self.scheduler.free_slots and not (
                    self._pcache is not None
                    and self._pcache.has_reclaimable()):
                for req in self.scheduler.preemption_victims(now):
                    self._preempt(req, now)
            admitted = self.scheduler.admit(now)
        for req in admitted:
            step_pf += self._prefill_work(req, req.prompt_len, self._admit)
        if self.chunk:
            budget = self.max_step_tokens - sum(
                1 for r in self.scheduler.active.values()
                if r.state is RequestState.DECODING)
            for slot in sorted(self.scheduler.active):
                req = self.scheduler.active[slot]
                while (budget > 0 and req.state is RequestState.PREFILLING):
                    n = min(self.chunk, req.prompt_len - req.prefill_pos,
                            budget)
                    if req.prefill_pos + n >= req.prompt_len:
                        # a finalizing chunk moves this slot into the decode
                        # batch of this same iteration — reserve one budget
                        # token for that decode, or defer the finalize
                        if n + 1 > budget:
                            n = budget - 1
                        if n <= 0:
                            break
                    got = self._prefill_work(req, n, self._run_chunk, n)
                    if not got:
                        break
                    budget -= got + (1 if req.state is RequestState.DECODING
                                     else 0)
                    step_pf += got
        self.stats["prefill_tokens"] += step_pf
        self.stats["max_step_prefill_tokens"] = max(
            self.stats["max_step_prefill_tokens"], step_pf)
        dec = [(slot, r) for slot, r in self.scheduler.active.items()
               if r.state is RequestState.DECODING]
        self.stats["max_step_total_tokens"] = max(
            self.stats["max_step_total_tokens"], step_pf + len(dec))
        if not dec:
            return step_pf > 0 or cancelled
        self.stats["decode_steps"] += 1
        if (self._injector is not None
                and self._injector.fail_step(self.stats["steps"])):
            # a transient device error mid-step consumes the donated pool
            # exactly like a real failed donated call would; step()'s
            # retry loop rebuilds from committed host state
            for leaf in jax.tree.leaves(self.state):
                leaf.delete()
            raise InjectedStepFailure(
                f"injected device error at step {self.stats['steps']}")
        if self.spec_tree:
            self._spec_tree_decode(dec)
            return True
        if self._can_fuse(dec):
            self._multi_decode(dec)
            return True
        logits, self.state = self._counted(self._dev(
            self._decode, self.qparams, self.state,
            self._push(self._last_tok,
                       self._io and self._io["tokens"], decode=True)),
            _DECODE_COUNTS)
        nxt = self._next_tokens(logits, dec)
        with TraceAnnotation("engine.emit", slots=len(dec)):
            now = self._now()
            for slot, req in dec:
                self._slot_pos[slot] += 1  # host mirror of the device cursor
                if req.replay_pos < len(req.output):
                    # resuming after preemption: this decode recomputed a
                    # token we already emitted — re-feed the recorded one,
                    # no append
                    tok = req.output[req.replay_pos]
                    req.replay_pos += 1
                    self._last_tok[slot] = tok
                    continue
                tok = int(nxt[slot])
                req.output.append(tok)
                req.replay_pos = len(req.output)
                self._last_tok[slot] = tok
                self.policy.on_tokens(req, 1)
                if req.should_stop():
                    self._retire(req, now)
        return True

    # -- fused multi-step decode lane ---------------------------------------
    def _can_fuse(self, dec: list[tuple[int, Request]]) -> bool:
        """Enter the device-resident lane only in pure decode steady state:
        no queued request (nothing to admit, nothing for a policy to
        preempt for), no in-flight prefill, every resident greedy and past
        its replay.  Anything else falls back to the single-step loop, so
        scheduling decisions are never deferred by a fused block."""
        if self.multi_step <= 1 or self.scheduler.queue:
            return False
        if any(r.state is not RequestState.DECODING
               for r in self.scheduler.active.values()):
            return False
        return all(req.temperature <= 0 and req.replay_pos >= len(req.output)
                   for _, req in dec)

    def _multi_decode(self, dec: list[tuple[int, Request]]) -> None:
        """One fused block: ``multi_step`` greedy decode iterations run in a
        single jitted scan with the argmax fed back on device; the host
        sees only the [n_slots, m] int32 token block.  A slot that stops
        mid-block (EOS or budget) commits its emitted prefix and the
        overshoot unwinds exactly like a rejected speculative suffix: the
        per-slot cursor rewinds (:func:`transformer.rewind_pos`) and the
        dead rows are overwritten in place by the next resident."""
        m = self.multi_step
        self.stats["decode_steps"] += m - 1       # step() counted one
        self.stats["multi_blocks"] += 1
        blk_dev, self.state = self._counted(self._dev(
            self._multi, self.qparams, self.state,
            self._push(self._last_tok,
                       self._io and self._io["tokens"], decode=True)),
            _DECODE_COUNTS)
        blk = self._fetch(blk_dev, decode=True)   # [n_slots, m] int32
        with TraceAnnotation("engine.emit", slots=len(dec)):
            now = self._now()
            stopped_early = False
            block_tokens = 0
            for slot, req in dec:
                emitted = 0
                for i in range(m):
                    tok = int(blk[slot, i])
                    req.output.append(tok)
                    req.replay_pos = len(req.output)
                    self._last_tok[slot] = tok
                    self.policy.on_tokens(req, 1)
                    emitted += 1
                    if req.should_stop():
                        self._retire(req, now)
                        break
                self._slot_pos[slot] += emitted
                self.stats["multi_tokens"] += emitted
                block_tokens += emitted
                if emitted < m:
                    stopped_early = True
            # a fused iteration emits up to len(dec) * m tokens: keep the
            # per-iteration stat honest (fusion never competes with
            # prefill work — it only runs when no PREFILLING slot or queue
            # exists, so the chunked token budget's decode-vs-prefill
            # packing is unaffected)
            self.stats["max_step_total_tokens"] = max(
                self.stats["max_step_total_tokens"], block_tokens)
        if stopped_early:
            # commit each stopped slot's emitted prefix; rows past it are
            # dead in-place entries until the next admission overwrites them
            self.state = T.rewind_pos(self.state, self._pos_device())

    # -- speculative decode lane -------------------------------------------
    def _row_token_fn(self, logits, dec: list[tuple[int, Request]]):
        """Fetch the verify logits under the decode-lane transfer
        discipline and return a ``(req, slot, i) -> int`` row sampler.

        The fetch shrinks exactly like :meth:`_next_tokens`: all-greedy
        pools argmax on device and ship [B, T] ints; bounded-top-k sampled
        pools ship [B, T, kmax] values+indices; only unbounded sampling
        falls back to the full [B, T, V] rows.  The returned sampler emits
        (or discards, for replay-stream alignment) the token the model
        chose at verify row ``i`` — identical across the three shapes."""
        rows = greedy_tok = vals_h = idx_h = None
        if all(req.temperature <= 0 for _, req in dec):
            greedy_tok = self._fetch(jnp.argmax(logits, -1), decode=True)
        else:
            ks = [req.top_k for _, req in dec if req.temperature > 0]
            if self.topk_preselect and all(
                    kk is not None and kk < self.cfg.vocab_size for kk in ks):
                kmax = max(ks)
                vals_h, idx_h = self._fetch(
                    self._device_topk(logits, kmax), decode=True)
            else:
                rows = self._fetch(logits, decode=True).astype(np.float32)

        def row_token(req: Request, slot: int, i: int) -> int:
            if greedy_tok is not None:
                return int(greedy_tok[slot, i])
            if rows is not None:
                return self._sample_token(req, rows[slot, i])
            return self._sample_candidates(req, vals_h[slot, i],
                                           idx_h[slot, i])

        return row_token

    def _tree_draft_for(self, req: Request, dr) -> tuple[list[int], list[int]]:
        """(tokens, draft-space parents) for one slot's tree window.

        A replaying (preempt-resumed) request drafts its recorded tokens as
        a chain — perfect drafts, so replay advances ``spec_tree + 1``
        positions per window and stays token-identical; the tail past the
        recorded output comes from the drafter (the model drafter's
        chain-0 prefix, or a fresh host chain draft).  Fresh requests get
        the drafter's tree proper."""
        n = self.spec_tree
        rec = list(req.output[req.replay_pos:req.replay_pos + n])
        if not rec:
            if self._drafter.kind == "model":
                return ([int(t) for t in dr[req.slot]],
                        list(self._drafter.tree_parents))
            ctx = req.prompt + req.output
            return self._drafter.draft_tree(ctx, n, self.spec_branch)
        if len(rec) < n:
            if self._drafter.kind == "model":
                rec += [int(t) for t in dr[req.slot, :n - len(rec)]]
            else:
                ctx = req.prompt + req.output[:req.replay_pos] + rec
                rec += self._drafter.draft(ctx, n - len(rec))
        return rec, chain_parents(n)

    def _spec_tree_decode(self, dec: list[tuple[int, Request]]) -> None:
        """One tree-verify pass over the decode pool: feed [root = last
        committed token, ``spec_tree`` tree-drafted nodes] per slot with
        per-row depths and ancestor bitmasks, walk the verified tree
        host-side for the longest accepted root-path, then compact the
        accepted path's scattered K/V rows into contiguous committed rows
        (``tree_commit``; a bare cursor rewind when every path is its
        chain prefix) — the rejected rows die in place."""
        n = self.spec_tree
        Tw = n + 1
        toks = np.zeros((self.n_slots, Tw), np.int32)
        toks[:, 0] = self._last_tok
        # every batched row needs a valid topology — inactive slots verify
        # a dummy chain whose garbage K/V rows the commit masks (keep=0)
        depth = np.tile(np.arange(Tw, dtype=np.int32), (self.n_slots, 1))
        anc = np.tile(((1 << (np.arange(Tw) + 1)) - 1).astype(np.int32),
                      (self.n_slots, 1))
        dr = None
        if self._drafter.kind == "model":
            rep = self._io and self._io["pos"]     # replicated on the mesh
            dr = self._fetch(self._dev(
                self._drafter.draft_tree_batch, self.qparams,
                self._push(self._h_last, rep, decode=True),
                self._push(self._last_tok, rep, decode=True),
                self._push(np.asarray(self._slot_pos, np.int32), rep,
                           decode=True)), decode=True)
        drafts: dict[int, list[int]] = {}
        parents: dict[int, list[int]] = {}
        for slot, req in dec:
            d_toks, d_par = self._tree_draft_for(req, dr)
            drafts[slot], parents[slot] = d_toks, d_par
            toks[slot, 1:] = d_toks
            dep, an = tree_depths_ancestors(d_par)
            depth[slot], anc[slot] = dep, an
        wsh = self._io and self._io["tree_window"]
        logits, hidden, self.state = self._dev(
            self._verify_tree, self.qparams, self.state,
            self._push(toks, self._io and self._io["verify_tokens"],
                       decode=True),
            self._push(depth, wsh, decode=True),
            self._push(anc, wsh, decode=True))
        self.stats["verify_steps"] += 1
        row_token = self._row_token_fn(logits, dec)
        hid = (self._fetch(hidden, decode=True).astype(np.float32)
               if self._drafter.kind == "model" else None)
        with TraceAnnotation("engine.emit", slots=len(dec)):
            # the commit's rollback base: each slot's cursor BEFORE this
            # window (window node w's K/V row sits at base + w)
            base = np.asarray(self._slot_pos, np.int32)
            sel = np.zeros((self.n_slots, n), np.int32)
            keep = np.zeros((self.n_slots,), np.int32)
            now = self._now()
            for slot, req in dec:
                # children of each window node in draft order; the walk is
                # unambiguous because siblings carry distinct tokens
                kids: dict[int, list[int]] = {}
                for i, p in enumerate(parents[slot]):
                    kids.setdefault(p + 1, []).append(i + 1)
                cur = 0                # window node whose row we sample
                path: list[int] = []   # accepted nodes, root-path order
                while True:
                    # row `cur` is the model's next-token distribution
                    # after consuming the root plus cur's ancestor chain —
                    # valid because reaching cur means that whole chain was
                    # accepted
                    replaying = req.replay_pos < len(req.output)
                    if replaying:
                        # the draw still runs (discarded) so a resumed
                        # sampled request re-consumes one draw per recorded
                        # token and its stream stays aligned — same rule as
                        # _next_tokens
                        if req.temperature > 0:
                            row_token(req, slot, cur)
                        tok = req.output[req.replay_pos]
                        req.replay_pos += 1
                    else:
                        tok = row_token(req, slot, cur)
                        req.output.append(tok)
                        req.replay_pos = len(req.output)
                        self.policy.on_tokens(req, 1)
                    self._last_tok[slot] = tok
                    if hid is not None:
                        self._h_last[slot] = hid[slot, cur]
                    nxt = next((c for c in kids.get(cur, ())
                                if int(toks[slot, c]) == tok), None)
                    if not replaying and kids.get(cur):
                        self.stats["spec_drafted"] += 1
                        self.stats["spec_accepted"] += int(nxt is not None)
                    if req.replay_pos >= len(req.output) and req.should_stop():
                        # the stopping token was drafted: commit its row
                        if nxt is not None:
                            path.append(nxt)
                        self._retire(req, now)
                        break
                    if nxt is None:
                        break
                    path.append(nxt)
                    cur = nxt
                committed = len(path)
                sel[slot, :committed] = path
                keep[slot] = committed
                self.stats["spec_accept_hist"][committed] += 1
                self._slot_pos[slot] += 1 + committed
        # every accepted path a chain prefix (always so at spec_branch=1):
        # its rows already sit at base + 1 .., so the gather would be an
        # identity and the commit is the bare cursor rewind
        w = np.arange(n)
        if np.all((sel == w + 1) | (w >= keep[:, None])):
            self.state = T.rewind_pos(self.state, self._pos_device())
            return
        # compact: gather each slot's accepted rows (base + sel) into
        # contiguous committed rows at base + 1 and land the new cursors;
        # inactive slots pass keep=0 and their unchanged cursor (no-op)
        csh = self._io and self._io["tree_commit"]
        self.state = self._dev(
            self._tree_commit, self.state,
            self._push(base, csh, decode=True),
            self._push(sel, csh, decode=True),
            self._push(keep, csh, decode=True),
            self._pos_device())

    def _pos_device(self):
        return self._push(np.asarray(self._slot_pos, np.int32),
                          self._io and self._io["pos"], decode=True)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of (non-replay) drafted tokens the verify step accepted."""
        d = self.stats["spec_drafted"]
        return self.stats["spec_accepted"] / d if d else float("nan")

    # -- drive to completion ----------------------------------------------
    def drain(self) -> None:
        """Step until the queue and all slots are empty.

        Terminates — never spins — when the remaining requests can make no
        progress: every terminal request (failed, cancelled, retired)
        leaves the queue/slots, so ``has_work()`` goes false; as a
        backstop, ``drain_stall_limit`` consecutive no-work iterations
        with work still pending raise instead of looping forever."""
        stalls = 0
        while self.scheduler.has_work():
            stalls = 0 if self.step() else stalls + 1
            if stalls >= self.drain_stall_limit:
                def _desc(r: Request) -> str:
                    where = f"@slot{r.slot}" if r.slot is not None else ""
                    return f"rid={r.rid}:{r.state.value}{where}"
                stuck = ([_desc(r) for r in self.scheduler.queue]
                         + [_desc(r) for r in
                            self.scheduler.active.values()])
                raise RuntimeError(
                    f"drain() stalled: {stalls} consecutive iterations did "
                    f"no work but {len(stuck)} request(s) are still "
                    f"pending [{', '.join(stuck)}]")

    def generate_all(self, prompts: list[list[int]],
                     max_new_tokens: int | list[int],
                     eos_id: int | None = None, *,
                     raise_on_error: bool = True) -> list[list[int]]:
        """Convenience: submit a ragged batch of prompts, run to completion,
        return outputs in submission order.

        A request whose admission/prefill raised finishes with ``.error``
        set and an empty output; that is indistinguishable from a real
        empty generation, so by default any failure raises
        :class:`RequestFailedError` (``.failures`` carries the requests).
        Pass ``raise_on_error=False`` to get the partial outputs and
        inspect ``.error`` per request instead."""
        budgets = (max_new_tokens if isinstance(max_new_tokens, list)
                   else [max_new_tokens] * len(prompts))
        reqs = [self.submit(p, m, eos_id) for p, m in zip(prompts, budgets)]
        self.drain()
        failures = [r for r in reqs if r.error is not None]
        if failures and raise_on_error:
            raise RequestFailedError(failures)
        return [r.output for r in reqs]
