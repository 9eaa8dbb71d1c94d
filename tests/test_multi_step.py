"""Device-resident decode loop: fused multi-step decode, donated decode
state, and the O(slots) per-token transfer discipline.

Covers:

* ``multi_decode_step`` emits exactly the tokens ``m`` sequential greedy
  ``decode_step`` calls would (argmax fed back on device), advances the
  cursor by ``m``, and a rewound block-state decodes on identically — the
  overshoot-rollback foundation;
* the engine's fused lane is token-identical to the single-step engine for
  every policy, chunked and atomic prefill, at ``m`` in {2, 4, 8}, with
  EOS/budget stops mid-block unwound through the cursor rewind;
* SSM/hybrid stacks silently keep the one-token loop (recurrent state
  cannot rewind), sampled/replaying slots fall back to single-step, and the
  spec lane takes precedence when both are enabled — all token-identical;
* donation: the decode step consumes (deletes) its input state buffers —
  the SLC pool updates in place, no per-token copy;
* transfer discipline: steady-state greedy decode moves exactly
  O(n_slots * m) int32 bytes per block and sampled decode O(n_slots * k)
  (device-side top-k pre-select), all through explicit transfers that
  survive a ``jax.transfer_guard("disallow")`` scope — so a future change
  cannot silently reintroduce per-step full-vocab or state copies;
* the top-k pre-select is bit-identical to full-vocab host sampling
  (``lax.top_k``'s tie order matches the host's stable sort).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def gqa_setup():
    cfg = ARCHS["llama3-8b"].reduced()
    from repro.models import model as M
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _trace(cfg, n=6, seed=11):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(l)).tolist()
               for l in rng.integers(3, 16, size=n)]
    budgets = [int(b) for b in rng.integers(2, 9, size=n)]
    return prompts, budgets

def _eos_at(full, start=2):
    """First index >= ``start`` whose token has not appeared earlier in
    ``full``: an eos there stops the plain engine exactly at that index."""
    return next(i for i in range(start, len(full)) if full[i] not in full[:i])



def _check_block_matches_steps(cfg, params, plens, max_len, m):
    """Prefill one slot per prompt length, then check that the fused
    ``m``-step block equals ``m`` argmax-fed ``decode_step`` calls, that the
    cursor advances by ``m``, and that the block state rewound to the
    sequential cursor decodes on bit for bit."""
    from repro.models import model as M
    from repro.models import transformer as T
    from repro.models.transformer import Runtime
    rt = Runtime()
    B = len(plens)
    state = M.init_decode_state(cfg, B, max_len + m - 1)
    for b, plen in enumerate(plens):
        toks = jnp.asarray(np.arange(1, plen + 1)[None], jnp.int32)
        _, one = M.prefill(params, cfg, {
            "inputs": toks, "lengths": jnp.array([plen], jnp.int32)},
            max_len, rt)
        state = T.write_slot(state, jnp.int32(b), one)
    tok0 = jnp.arange(3, 3 + 2 * B, 2, dtype=jnp.int32)
    st, tok, seq = state, tok0, []
    for _ in range(m):
        lg, st = M.decode_step(params, cfg, st, tok, rt)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        seq.append(np.asarray(tok))
    blk, mstate = M.multi_decode_step(params, cfg, state, tok0, m, rt)
    np.testing.assert_array_equal(np.asarray(blk), np.stack(seq, axis=1))
    np.testing.assert_array_equal(np.asarray(mstate["pos"]),
                                  np.asarray(state["pos"]) + m)
    for a, b in zip(jax.tree.leaves(mstate["groups"]),
                    jax.tree.leaves(st["groups"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # overshoot rollback: rewind the fused state to the sequential
    # cursor and the next decode step must match bit-for-bit
    rewound = T.rewind_pos(mstate, np.asarray(st["pos"]))
    lg_a, _ = M.decode_step(params, cfg, rewound, tok, rt)
    lg_b, _ = M.decode_step(params, cfg, st, tok, rt)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))


# ---------------------------------------------------------------------------
# model level
# ---------------------------------------------------------------------------
class TestMultiDecodeStep:
    def test_matches_sequential_greedy_decode(self, gqa_setup):
        """The fused scan's [B, m] token block equals m sequential
        argmax-fed decode steps, the cursor advances by m, and rewinding
        the block state to the sequential cursor decodes on identically
        (overshoot rollback is exact)."""
        cfg, params = gqa_setup
        _check_block_matches_steps(cfg, params, (4, 6, 5), max_len=32, m=4)

    def test_matches_sequential_greedy_decode_mha_to_pool_end(self):
        """The same on an MHA stack whose head_dim (24) is not a multiple
        of 128, at four slots, one of which appends into the pool's last
        row during the block."""
        from repro.models import model as M
        cfg = dataclasses.replace(ARCHS["phi3-mini-3.8b"].reduced(),
                                  head_dim=24)
        assert cfg.n_kv_heads == cfg.n_heads
        params = M.init_params(jax.random.key(0), cfg)
        _check_block_matches_steps(cfg, params, (4, 31, 9, 1), max_len=32,
                                   m=4)

    def test_encdec_rejected(self):
        from repro.models import model as M
        from repro.models.transformer import Runtime
        cfg = ARCHS["whisper-tiny"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        with pytest.raises(NotImplementedError):
            M.multi_decode_step(params, cfg, {},
                                jnp.zeros((2,), jnp.int32), 4, Runtime())


# ---------------------------------------------------------------------------
# engine-level parity
# ---------------------------------------------------------------------------
class TestEngineMultiStepParity:
    def test_all_policies_chunked_and_not(self, gqa_setup):
        """Greedy fused decode is token-identical to the single-step engine
        for all four policies, chunked and atomic prefill, at m=4 — and at
        m in {2, 8} — with fused blocks actually exercised."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, budgets = _trace(cfg)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32).generate_all(prompts, budgets)
        for policy in ("fifo", "priority", "sjf", "fair"):
            for chunk in (None, 4):
                eng = ContinuousBatchingEngine(
                    cfg, params, n_slots=2, max_len=32, policy=policy,
                    chunk=chunk, multi_step=4)
                assert eng.generate_all(prompts, budgets) == ref, \
                    (policy, chunk)
                assert eng.stats["multi_blocks"] > 0, (policy, chunk)
        for m in (2, 8):
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=32, multi_step=m)
            assert eng.generate_all(prompts, budgets) == ref, m
            assert eng.stats["multi_blocks"] > 0, m

    def test_spec_lane_takes_precedence(self, gqa_setup):
        """spec_tree > 0 (a chain, spec_branch=1) and multi_step > 1
        together: the spec lane runs (it already amortizes the weight read
        over k+1 tokens) and output stays token-identical."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, budgets = _trace(cfg)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32).generate_all(prompts, budgets)
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=32,
                                       spec_tree=4, spec_branch=1,
                                       multi_step=4)
        assert eng.generate_all(prompts, budgets) == ref
        assert eng.stats["verify_steps"] > 0
        assert eng.stats["multi_blocks"] == 0

    def test_eos_mid_block_stops_exactly_and_backfills(self, gqa_setup):
        """An EOS landing inside a fused block must stop the request exactly
        where the single-step engine would — the overshoot rows unwind via
        the cursor rewind — and the freed slot backfills."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg)
        full = ContinuousBatchingEngine(
            cfg, params, n_slots=1, max_len=32).generate_all(
                [prompts[0]], [8])[0]
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                       multi_step=4)
        i = _eos_at(full)
        r_eos = eng.submit(prompts[0], 8, eos_id=full[i])
        eng.drain()                     # queue must be empty for fusion
        assert eng.stats["multi_blocks"] > 0
        r_next = eng.submit(list(reversed(prompts[0])), 3)
        eng.drain()
        assert r_eos.output == full[:i + 1]
        assert len(r_next.output) == 3

    def test_budget_overshoot_unwound(self, gqa_setup):
        """A budget that is not a multiple of m stops mid-block; the emitted
        prefix must equal the single-step run and the next resident of the
        slot must be unaffected by the dead overshoot rows."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=1, max_len=32).generate_all(
                prompts[:3], [5, 7, 6])
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                       multi_step=4)
        assert eng.generate_all(prompts[:3], [5, 7, 6]) == ref
        assert eng.stats["multi_blocks"] > 0

    def test_ssm_keeps_single_step(self):
        from repro.models import model as M
        from repro.serve.engine import ContinuousBatchingEngine
        cfg = ARCHS["mamba2-2.7b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=32,
                                       multi_step=4)
        assert eng.multi_step == 1      # recurrent state cannot rewind
        prompts, budgets = _trace(cfg, n=3)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32).generate_all(prompts, budgets)
        assert eng.generate_all(prompts, budgets) == ref

    def test_sampled_slots_fall_back_to_single_step(self, gqa_setup):
        """A sampled resident disables fusion (the fused block is greedy
        argmax); outputs must match the m=1 engine stream-for-stream."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg, n=4)

        def run(m):
            eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                           max_len=32, multi_step=m)
            reqs = [eng.submit(p, 6, temperature=0.8, top_k=16,
                               seed=100 + i)
                    for i, p in enumerate(prompts)]
            eng.drain()
            return [r.output for r in reqs], eng
        (a, _), (b, eng_m) = run(1), run(4)
        assert a == b
        assert eng_m.stats["multi_blocks"] == 0

    def test_invalid_multi_step_rejected(self, gqa_setup):
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                     multi_step=0)


# ---------------------------------------------------------------------------
# donation + transfer discipline
# ---------------------------------------------------------------------------
class TestTransferDiscipline:
    def _steady_engine(self, cfg, params, **kw):
        """Two residents decoding with an empty queue — pure decode steady
        state, prefill transfers already behind us."""
        from repro.serve.engine import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                       **kw)
        prompts, _ = _trace(cfg, n=2)
        for p in prompts:
            eng.submit(p, 40)
        eng.step()                      # admit + prefill + first decode
        return eng

    def test_decode_state_is_donated_in_place(self, gqa_setup):
        """donate_argnums on the decode step: the previous state's buffers
        are consumed (deleted) by the next step — the SLC pool updates in
        place, never copied per token."""
        cfg, params = gqa_setup
        eng = self._steady_engine(cfg, params)
        leaf = jax.tree.leaves(eng.state)[0]
        eng.step()
        assert leaf.is_deleted()
        # fused lane donates too
        eng4 = self._steady_engine(cfg, params, multi_step=4)
        leaf4 = jax.tree.leaves(eng4.state)[0]
        eng4.step()
        assert leaf4.is_deleted()

    def test_greedy_transfer_is_O_slots_per_block(self, gqa_setup):
        """Steady-state greedy decode moves exactly 2 * n_slots int32 per
        single step (last-token push + argmax fetch) and
        (1 + m) * n_slots int32 per fused block — never the [B, V] logits
        or any state leaf."""
        cfg, params = gqa_setup
        eng = self._steady_engine(cfg, params)
        base = eng.stats["decode_xfer_bytes"]
        for _ in range(3):
            eng.step()
        assert eng.stats["decode_xfer_bytes"] - base == 3 * (2 * 2 * 4)

        eng4 = self._steady_engine(cfg, params, multi_step=4)
        base = eng4.stats["decode_xfer_bytes"]
        blocks0 = eng4.stats["multi_blocks"]
        for _ in range(2):
            eng4.step()
        assert eng4.stats["multi_blocks"] == blocks0 + 2
        assert (eng4.stats["decode_xfer_bytes"] - base
                == 2 * (2 * 4 + 2 * 4 * 4))   # push [2] + fetch [2, 4] int32

    def test_sampled_transfer_is_O_slots_times_k(self, gqa_setup):
        """Sampled decode with bounded top_k ships [n_slots, k] values +
        indices (device pre-select), not [n_slots, V] rows."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg, n=2)
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=64)
        for i, p in enumerate(prompts):
            eng.submit(p, 40, temperature=0.8, top_k=16, seed=i)
        eng.step()
        base = eng.stats["decode_xfer_bytes"]
        for _ in range(3):
            eng.step()
        per_step = (eng.stats["decode_xfer_bytes"] - base) / 3
        # push [2] i32 + fetch [2, 16] f32 + [2, 16] i32
        assert per_step == 2 * 4 + 2 * 16 * 4 * 2
        assert per_step < cfg.vocab_size        # nowhere near a vocab row

    def test_decode_steps_survive_transfer_guard_disallow(self, gqa_setup):
        """Every steady-state transfer is explicit (device_put/device_get),
        so serving keeps working inside jax.transfer_guard("disallow") —
        the scope that rejects implicit host<->device copies on
        accelerator backends."""
        cfg, params = gqa_setup
        eng = self._steady_engine(cfg, params, multi_step=4)
        out_before = {s: list(r.output)
                      for s, r in eng.scheduler.active.items()}
        with jax.transfer_guard("disallow"):
            for _ in range(2):
                eng.step()
        for s, r in eng.scheduler.active.items():
            assert len(r.output) > len(out_before[s])

    def test_topk_preselect_bit_identical_and_optional(self, gqa_setup):
        """Pre-select on vs off: identical sampled streams (lax.top_k's tie
        order matches the host stable sort); top_k=None falls back to the
        full-vocab row without changing the stream either."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg, n=4)

        def run(pre, top_k):
            eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                           max_len=32, topk_preselect=pre)
            reqs = [eng.submit(p, 6, temperature=0.8, top_k=top_k,
                               seed=100 + i)
                    for i, p in enumerate(prompts)]
            eng.drain()
            return [r.output for r in reqs]
        assert run(True, 16) == run(False, 16)
        assert run(True, None) == run(False, None)

    def test_spec_verify_fetch_shrinks_and_stays_exact(self, gqa_setup):
        """The spec lane's sampled verify fetch uses the same pre-select:
        streams identical with it on and off, and with spec off."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg, n=4)

        def run(spec_tree, pre):
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=32, spec_tree=spec_tree,
                spec_branch=1, topk_preselect=pre)
            reqs = [eng.submit(p, 6, temperature=0.8, top_k=16,
                               seed=100 + i)
                    for i, p in enumerate(prompts)]
            eng.drain()
            return [r.output for r in reqs]
        assert run(4, True) == run(4, False) == run(0, True)
