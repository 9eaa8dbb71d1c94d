"""Speculative decode lane: drafters, the batched verify step, cursor
rollback, and the engine-level guarantee that greedy speculative decode is
token-identical to the plain engine.

Covers:

* the n-gram (prompt-lookup) drafter proposes continuations of repeated
  context and falls back to repeat-last;
* ``verify_step`` over a chain window is bit-identical to sequential
  ``decode_step`` calls (the acceptance test's foundation), and a rewound
  verify state decodes on identically (rollback exactness); over a tree it
  matches sequential decode along every root-path;
* draft-tree topology helpers and ``tree_commit``/``path_gather``
  compaction are exact;
* spec decode (``spec_tree``; a chain is ``spec_branch=1``) outputs equal
  the non-speculative engine for every policy, chunked and unchunked, at
  several window sizes and branch factors — with a worst-case
  (never-right) and an oracle (always-right) drafter bounding both ends;
  a chain window commits by cursor rewind, never by ``tree_commit``;
* preempt-resume replay rides the spec lane (recorded tokens as perfect
  chain drafts) and reproduces the uncontended run;
* sampled requests stay stream-exact: one RNG draw per emitted token, so
  seeded sampling with and without speculation emits the same tokens;
* the MTP drafter (DeepSeek head) beams batched drafts and stays lossless.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.serve.drafter import (Drafter, NGramDrafter, chain_parents,
                                 make_drafter, tree_depths_ancestors)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# drafters (no model)
# ---------------------------------------------------------------------------
class TestNGramDrafter:
    def test_prompt_lookup_proposes_continuation(self):
        d = NGramDrafter(max_n=3)
        #            0  1  2  3  4  5  6  7
        ctx = [5, 6, 7, 8, 9, 5, 6, 7]
        # trailing 3-gram (5,6,7) recurs at 0; continuation is 8, 9, 5, ...
        assert d.draft(ctx, 3) == [8, 9, 5]

    def test_falls_back_to_repeat_last(self):
        d = NGramDrafter()
        assert d.draft([1, 2, 3, 4], 3) == [4, 4, 4]
        assert d.draft([9], 2) == [9, 9]

    def test_short_match_pads_with_last(self):
        d = NGramDrafter(max_n=2)
        ctx = [1, 2, 3, 1, 2]       # (1,2) recurs at 0; continuation [3,1,2]
        assert d.draft(ctx, 4) == [3, 1, 2, 2]

    def test_k_longer_than_context(self):
        """The draft budget can exceed the whole context: the continuation
        pads with its own last token, the fallback repeats the tail."""
        d = NGramDrafter(max_n=3)
        assert d.draft([4, 5, 4], 8) == [5, 4, 4, 4, 4, 4, 4, 4]
        assert d.draft([5, 6], 5) == [6, 6, 6, 6, 6]

    def test_max_n_1_degenerate(self):
        """max_n=1 is pure last-token lookup — the most recent earlier
        occurrence of the final token supplies the continuation."""
        d = NGramDrafter(max_n=1)
        assert d.draft([1, 2, 1, 3, 1], 2) == [3, 1]
        with pytest.raises(ValueError):
            NGramDrafter(max_n=0)

    def test_tree_collapses_to_chain_on_repeated_continuations(self):
        """Two matches whose continuations start with the same token are
        one candidate (siblings must be distinct), so draft_tree degrades
        to exactly the linear draft's chain."""
        d = NGramDrafter(max_n=3)
        ctx = [1, 2, 7, 0, 1, 2, 7, 9, 1, 2]    # both (1,2) matches -> 7
        toks, par = d.draft_tree(ctx, 3, branch=2)
        assert toks == d.draft(ctx, 3)
        assert par == chain_parents(3)

    def test_tree_branches_on_distinct_candidates(self):
        """Distinct first tokens branch: the best match keeps a chain of
        the remaining budget, the runner-up hangs one node off the root."""
        d = NGramDrafter(max_n=3)
        ctx = [1, 2, 5, 1, 2, 7, 1, 2]
        assert d._candidates(ctx, 3, 2) == [[7, 1, 2], [5, 1, 2]]
        toks, par = d.draft_tree(ctx, 3, branch=2)
        assert toks == [7, 1, 5] and par == [-1, 0, -1]

    def test_tree_no_match_falls_back_to_repeat_last_chain(self):
        toks, par = NGramDrafter().draft_tree([5, 6], 3, branch=2)
        assert toks == [6, 6, 6] and par == chain_parents(3)

    def test_make_drafter_parsing(self):
        cfg = ARCHS["llama3-8b"].reduced()
        assert isinstance(make_drafter("ngram", cfg, None, 4, 2),
                          NGramDrafter)
        assert make_drafter("ngram:5", cfg, None, 4, 2).max_n == 5
        inst = NGramDrafter()
        assert make_drafter(inst, cfg, None, 4, 2) is inst
        with pytest.raises(ValueError):
            make_drafter("oracle", cfg, None, 4, 2)
        with pytest.raises(ValueError):
            make_drafter("mtp", cfg, None, 4, 2)  # llama has no MTP head


# ---------------------------------------------------------------------------
# verify step (model level)
# ---------------------------------------------------------------------------
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



class TestVerifyStep:
    @pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v3-671b"])
    def test_verify_logits_match_sequential_decode(self, arch):
        """Over a chain window (``depth``/``anc`` of a chain), row i of the
        verify logits must equal the i-th sequential decode step's logits
        bit-for-bit (GQA int8 path and absorbed MLA), and the rewound
        verify state must decode on identically to the sequential state —
        the rollback-exactness property."""
        from repro.models import model as M
        from repro.models import transformer as T
        from repro.models.transformer import Runtime
        cfg = ARCHS[arch].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        rt = Runtime()
        B, max_len, steps = 3, 32, 4
        state = M.init_decode_state(cfg, B, max_len)
        for b, plen in enumerate((4, 6, 5)):
            toks = jnp.asarray(np.arange(1, plen + 1)[None], jnp.int32)
            _, one = M.prefill(params, cfg, {
                "inputs": toks, "lengths": jnp.array([plen], jnp.int32)},
                max_len, rt)
            state = T.write_slot(state, jnp.int32(b), one)
        tok = jnp.array([3, 5, 7], jnp.int32)
        st, seq_logits = state, []
        for _ in range(steps):
            lg, st = M.decode_step(params, cfg, st, tok, rt)
            seq_logits.append(np.asarray(lg))
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
        greedy = [np.argmax(l, -1) for l in seq_logits]
        fed = jnp.asarray(np.stack(
            [[3, 5, 7]] + greedy[:steps - 1], axis=1), jnp.int32)
        depth_l, anc_l = tree_depths_ancestors(chain_parents(steps - 1))
        assert depth_l == [0, 1, 2, 3] and anc_l == [1, 3, 7, 15]
        depth = jnp.tile(jnp.asarray(depth_l, jnp.int32)[None], (B, 1))
        anc = jnp.tile(jnp.asarray(anc_l, jnp.int32)[None], (B, 1))
        vlog, hidden, vstate = M.verify_step(params, cfg, state, fed, rt,
                                             depth, anc)
        vlog = np.asarray(vlog)
        for i in range(steps):
            np.testing.assert_array_equal(vlog[:, i], seq_logits[i])
        assert hidden.shape == (B, steps, cfg.d_model)
        np.testing.assert_array_equal(np.asarray(vstate["pos"]),
                                      np.asarray(state["pos"]) + steps)
        # rollback: rewind the cursor to the sequential position and decode
        rewound = T.rewind_pos(vstate, np.asarray(st["pos"]))
        lg_a, _ = M.decode_step(params, cfg, rewound, tok, rt)
        lg_b, _ = M.decode_step(params, cfg, st, tok, rt)
        np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))

    def test_ssm_stack_rejected(self):
        from repro.models import model as M
        from repro.models.transformer import Runtime
        cfg = ARCHS["mamba2-2.7b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        state = M.init_decode_state(cfg, 2, 16)
        depth = jnp.tile(jnp.arange(3, dtype=jnp.int32)[None], (2, 1))
        with pytest.raises(NotImplementedError):
            M.verify_step(params, cfg, state, jnp.zeros((2, 3), jnp.int32),
                          Runtime(), depth, 2 * (1 << depth) - 1)

    @pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v3-671b"])
    def test_tree_verify_matches_sequential_and_commits_exactly(self, arch):
        """Tree mode: chain-prefix rows of the ancestor-masked verify
        logits equal sequential ``decode_step`` logits bit-for-bit; the
        row past the skipped junk sibling sees the same visible values at
        shifted lanes, so it matches up to float reduction order (~1 ulp)
        with the greedy choice preserved — and ``tree_commit`` compacts
        the accepted path into a state that decodes on like the
        sequential state (same tolerance, same argmax)."""
        from repro.models import model as M
        from repro.models import transformer as T
        from repro.models.transformer import Runtime
        cfg = ARCHS[arch].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        rt = Runtime()
        B, max_len = 3, 32
        state = M.init_decode_state(cfg, B, max_len)
        for b, plen in enumerate((4, 6, 5)):
            toks = jnp.asarray(np.arange(1, plen + 1)[None], jnp.int32)
            _, one = M.prefill(params, cfg, {
                "inputs": toks, "lengths": jnp.array([plen], jnp.int32)},
                max_len, rt)
            state = T.write_slot(state, jnp.int32(b), one)
        tok = jnp.array([3, 5, 7], jnp.int32)
        st, seq_logits = state, []
        for _ in range(3):
            lg, st = M.decode_step(params, cfg, st, tok, rt)
            seq_logits.append(np.asarray(lg))
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
        greedy = [np.argmax(l, -1).astype(np.int32) for l in seq_logits]
        # window: w0 = root, w1 = model's choice, w2 = junk sibling of w1
        # (distinct token, child of the root), w3 = next choice under w1
        junk = (greedy[0] + 1) % cfg.vocab_size
        fed = jnp.asarray(np.stack(
            [np.array([3, 5, 7], np.int32), greedy[0], junk, greedy[1]],
            axis=1), jnp.int32)
        depth_l, anc_l = tree_depths_ancestors([-1, -1, 0])
        assert depth_l == [0, 1, 1, 2] and anc_l == [1, 3, 5, 11]
        depth = jnp.tile(jnp.asarray(depth_l, jnp.int32)[None], (B, 1))
        anc = jnp.tile(jnp.asarray(anc_l, jnp.int32)[None], (B, 1))
        vlog, hidden, vstate = M.verify_step(params, cfg, state, fed, rt,
                                             depth=depth, anc=anc)
        vlog = np.asarray(vlog)
        np.testing.assert_array_equal(vlog[:, 0], seq_logits[0])
        np.testing.assert_array_equal(vlog[:, 1], seq_logits[1])
        # row 3's path skips the dead sibling at cache offset base + 2:
        # masked keys weigh exactly zero but the SIMD reductions associate
        # across lanes differently, so only reduction-order-level equality
        # holds there — the greedy choice (what acceptance compares) must
        # still agree
        np.testing.assert_allclose(vlog[:, 3], seq_logits[2], atol=1e-4,
                                   rtol=0)
        np.testing.assert_array_equal(np.argmax(vlog[:, 3], -1), greedy[2])
        assert hidden.shape == (B, 4, cfg.d_model)
        # commit the accepted root-path (w1, w3) on every slot: w3's row
        # moves down over the dead sibling, the cursor lands at base + 3
        base = jnp.asarray(np.asarray(state["pos"], np.int32))
        sel = jnp.asarray(np.tile([[1, 3, 0]], (B, 1)), jnp.int32)
        keep = jnp.full((B,), 2, jnp.int32)
        committed = M.tree_commit(vstate, base, sel, keep, base + 3)
        np.testing.assert_array_equal(np.asarray(committed["pos"]),
                                      np.asarray(st["pos"]))
        # w3's committed K/V carries the same reduction-order delta, so
        # decode-on agrees to the same tolerance and picks the same token
        lg_a, _ = M.decode_step(params, cfg, committed, tok, rt)
        lg_b, _ = M.decode_step(params, cfg, st, tok, rt)
        np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                                   atol=1e-4, rtol=0)
        np.testing.assert_array_equal(np.argmax(np.asarray(lg_a), -1),
                                      np.argmax(np.asarray(lg_b), -1))


# ---------------------------------------------------------------------------
# tree topology helpers + path compaction (pure functions)
# ---------------------------------------------------------------------------
class TestTreeTopology:
    def test_chain_parents(self):
        assert chain_parents(4) == [-1, 0, 1, 2]
        assert chain_parents(1) == [-1]
        assert chain_parents(0) == []

    def test_chain_depths_and_ancestors(self):
        depth, anc = tree_depths_ancestors(chain_parents(3))
        assert depth == [0, 1, 2, 3]
        assert anc == [1, 3, 7, 15]          # (1 << (i+1)) - 1

    def test_branchy_depths_and_ancestors(self):
        # w1, w2 children of the root; w3 child of w1; w4 child of w2
        depth, anc = tree_depths_ancestors([-1, -1, 0, 1])
        assert depth == [0, 1, 1, 2, 2]
        assert anc == [1, 3, 5, 11, 21]

    def test_non_topological_parents_rejected(self):
        with pytest.raises(ValueError):
            tree_depths_ancestors([0])       # self/forward reference
        with pytest.raises(ValueError):
            tree_depths_ancestors([-1, 2])
        with pytest.raises(ValueError):
            tree_depths_ancestors([-2])

    def test_mtp_chain_lengths(self):
        from repro.models.transformer import mtp_chain_lengths
        assert mtp_chain_lengths(4, 2) == [2, 2]
        assert mtp_chain_lengths(5, 2) == [3, 2]
        assert mtp_chain_lengths(3, 5) == [1, 1, 1]   # branch caps at n
        assert mtp_chain_lengths(4, 1) == [4]         # branch=1 == chain

    def test_path_gather_matches_numpy_oracle(self):
        """Accepted rows move from base + sel[w] to base + 1 + w; rows
        past keep (and every other row) stay byte-identical."""
        from repro.core import kvcache as KV
        rng = np.random.default_rng(0)
        L, B, S, H = 2, 2, 8, 3
        buf = rng.standard_normal((L, B, S, H)).astype(np.float32)
        base = np.array([2, 3], np.int32)
        sel = np.array([[1, 3], [2, 0]], np.int32)    # pad past keep is 0
        keep = np.array([2, 1], np.int32)
        out = np.asarray(KV.path_gather(jnp.asarray(buf), base, sel, keep))
        exp = buf.copy()
        for b in range(B):
            rows = buf[:, b, base[b] + sel[b]]        # gather-then-write
            for w in range(keep[b]):
                exp[:, b, base[b] + 1 + w] = rows[:, w]
        np.testing.assert_array_equal(out, exp)

    def test_pool_headroom_rule(self):
        from repro.core import kvcache as KV
        assert KV.pool_headroom() == 0
        assert KV.pool_headroom(spec_tree=6) == 6
        assert KV.pool_headroom(multi_step=4) == 3
        assert KV.pool_headroom(spec_tree=5, multi_step=4) == 5
        assert KV.pool_headroom(spec_tree=2, multi_step=4) == 3
        with pytest.raises(ValueError):
            KV.pool_headroom(multi_step=0)


# ---------------------------------------------------------------------------
# engine-level parity
# ---------------------------------------------------------------------------
class _ConstantDrafter(Drafter):
    """Worst case: always proposes the same token (never right unless the
    model actually loops on it)."""
    name, kind = "const", "host"

    def __init__(self, tok):
        self.tok = tok

    def draft(self, context, k):
        return [self.tok] * k


class _OracleDrafter(Drafter):
    """Best case: replays a precomputed reference continuation — accepts at
    ~100%, so verify_steps collapses by ~(k+1)x."""
    name, kind = "oracle", "host"

    def __init__(self, table):
        self.table = table               # prompt tuple -> full output list

    def draft(self, context, k):
        for (prompt, out) in self.table:
            n = len(prompt)
            if context[:n] == prompt:
                done = len(context) - n
                cont = out[done:done + k]
                return (cont + [context[-1]] * k)[:k]
        return [context[-1]] * k


def _no_tree_commit(*_):
    raise AssertionError("a chain window dispatched tree_commit")


BRANCHES = pytest.mark.parametrize("branch", [1, 2], ids=["chain", "tree"])


class TestTreeSpecParity:
    @BRANCHES
    def test_all_policies_chunked_and_not(self, gqa_setup, branch):
        """Acceptance: greedy spec decode, chain (``spec_branch=1``) and
        tree, is token-identical to the non-speculative engine for all
        four policies, chunked and unchunked, at several window sizes; the
        accept histogram covers every verify pass.  A chain's accepted
        path is always its chain prefix, so it commits by cursor rewind
        and never dispatches ``tree_commit``."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, budgets = _trace(cfg)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32).generate_all(prompts, budgets)
        for policy in ("fifo", "priority", "sjf", "fair"):
            for chunk in (None, 4):
                eng = ContinuousBatchingEngine(
                    cfg, params, n_slots=2, max_len=32, policy=policy,
                    chunk=chunk, spec_tree=4, spec_branch=branch)
                if branch == 1:
                    eng._tree_commit = _no_tree_commit
                assert eng.generate_all(prompts, budgets) == ref, \
                    (policy, chunk)
                assert eng.stats["verify_steps"] > 0
                assert eng.stats["spec_drafted"] > 0
                hist = eng.stats["spec_accept_hist"]
                assert len(hist) == 5
                # one histogram entry per active slot per verify pass
                assert sum(hist) >= eng.stats["verify_steps"]
        for n in (2, 8):
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=32, spec_tree=n,
                spec_branch=branch)
            assert eng.generate_all(prompts, budgets) == ref, n

    def test_branch_sweep_and_window_sizes(self, gqa_setup):
        """spec_branch only redistributes the draft budget across chains —
        outputs stay identical at every branch factor and window size."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, budgets = _trace(cfg)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32).generate_all(prompts, budgets)
        for n, branch in ((4, 1), (4, 3), (2, 2), (8, 2)):
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=32, spec_tree=n,
                spec_branch=branch)
            assert eng.generate_all(prompts, budgets) == ref, (n, branch)

    def test_spec_k_option_is_gone(self, gqa_setup):
        """A chain is ``spec_tree=k, spec_branch=1``: the old linear-lane
        option is refused, not silently ignored."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        with pytest.raises(TypeError):
            ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=32,
                                     spec_k=4)

    @BRANCHES
    def test_worst_and_best_case_drafters(self, gqa_setup, branch):
        """Draft quality is a pure performance knob: a never-right drafter
        only costs verify passes, and an oracle drafter (a chain from the
        base ``draft_tree``) accepts (nearly) everything and cuts verify
        steps by ~(k+1)x.  Both stay token-identical."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, budgets = _trace(cfg)
        ref_eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=32)
        ref = ref_eng.generate_all(prompts, budgets)
        base_steps = ref_eng.stats["decode_steps"]

        worst = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32, spec_tree=4,
            spec_branch=branch,
            drafter=_ConstantDrafter(tok=cfg.vocab_size - 1))
        assert worst.generate_all(prompts, budgets) == ref

        oracle = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32, spec_tree=4,
            spec_branch=branch,
            drafter=_OracleDrafter(list(zip(prompts, ref))))
        assert oracle.generate_all(prompts, budgets) == ref
        assert oracle.acceptance_rate > 0.9
        assert oracle.stats["verify_steps"] < base_steps / 2

    @BRANCHES
    def test_eos_inside_tree_window(self, gqa_setup, branch):
        """An accepted window node that equals eos stops the request
        exactly where the plain engine would — no committed tokens past
        eos leak from the window, and the slot backfills."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg)
        full = ContinuousBatchingEngine(
            cfg, params, n_slots=1, max_len=32).generate_all([prompts[0]], [8])[0]
        eng = ContinuousBatchingEngine(
            cfg, params, n_slots=1, max_len=32, spec_tree=4,
            spec_branch=branch,
            drafter=_OracleDrafter([(prompts[0], full)]))
        i = _eos_at(full)
        r_eos = eng.submit(prompts[0], 8, eos_id=full[i])
        r_next = eng.submit(list(reversed(prompts[0])), 3)
        eng.drain()
        assert r_eos.output == full[:i + 1]
        assert len(r_next.output) == 3

    @BRANCHES
    def test_spec_tree_ignored_for_ssm(self, branch):
        from repro.models import model as M
        from repro.serve.engine import ContinuousBatchingEngine
        cfg = ARCHS["mamba2-2.7b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=32,
                                       spec_tree=4, spec_branch=branch)
        assert eng.spec_tree == 0            # recurrent state cannot rewind
        prompts, budgets = _trace(cfg, n=3)
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32).generate_all(prompts, budgets)
        assert eng.generate_all(prompts, budgets) == ref

    def test_window_and_branch_validation(self, gqa_setup):
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        with pytest.raises(ValueError):      # anc bitmask is int32: n <= 30
            ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                     spec_tree=31)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                     spec_tree=4, spec_branch=0)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                     spec_tree=-1)


class TestTreeSpecPreemptionAndSampling:
    @BRANCHES
    def test_preempted_request_reproduces_unpreempted_output(self, gqa_setup,
                                                             branch):
        """Preempt-resume under the spec lane: replay drafts the recorded
        tokens as a chain of perfect drafts; the resumed output equals the
        uncontended run token-for-token."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg)
        solo = ContinuousBatchingEngine(
            cfg, params, n_slots=1, max_len=48).generate_all([prompts[0]], [14])[0]
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=48,
                                       policy="fair:3", chunk=4, spec_tree=4,
                                       spec_branch=branch)
        r1 = eng.submit(prompts[0], 14, user="A")
        r2 = eng.submit(prompts[1], 6, user="B")
        eng.drain()
        assert r1.n_preemptions >= 1
        assert r1.output == solo
        assert len(r2.output) == 6

    def test_preemptive_priority_unchunked(self, gqa_setup):
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg)
        solo = ContinuousBatchingEngine(
            cfg, params, n_slots=1, max_len=48).generate_all([prompts[2]], [10])[0]
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=48,
                                       policy="priority:preempt", spec_tree=2,
                                       spec_branch=1)
        lo = eng.submit(prompts[2], 10, priority=0)
        for _ in range(3):
            eng.step()
        hi = eng.submit(prompts[3], 3, priority=9)
        eng.drain()
        assert lo.n_preemptions >= 1
        assert lo.output == solo
        assert len(hi.output) == 3

    @BRANCHES
    def test_sampled_request_preempted_under_tree_reproduces_solo(
            self, gqa_setup, branch):
        """Regression: replay rows in the accept walk must still consume
        one RNG draw per recorded token (like the non-spec replay path),
        or a sampled request that is preempted and resumed under the spec
        lane diverges from its uncontended run."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg)
        solo_eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=48)
        solo = solo_eng.submit(prompts[0], 14, temperature=0.8, top_k=16,
                               seed=7)
        solo_eng.drain()
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=48,
                                       policy="fair:3", chunk=4, spec_tree=4,
                                       spec_branch=branch)
        r1 = eng.submit(prompts[0], 14, temperature=0.8, top_k=16, seed=7,
                        user="A")
        r2 = eng.submit(prompts[1], 6, user="B")
        eng.drain()
        assert r1.n_preemptions >= 1
        assert r1.output == solo.output

    @BRANCHES
    def test_sampling_is_stream_exact_under_tree_speculation(self, gqa_setup,
                                                              branch):
        """One RNG draw per emitted token and acceptance = 'node token
        equals the sampled token', so seeded sampling emits identical
        streams with and without the spec lane."""
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        prompts, _ = _trace(cfg, n=4)

        def run(n):
            eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                           max_len=32, spec_tree=n,
                                           spec_branch=branch)
            reqs = [eng.submit(p, 6, temperature=0.8, top_k=16, seed=100 + i)
                    for i, p in enumerate(prompts)]
            eng.drain()
            return [r.output for r in reqs]

        assert run(0) == run(4)


class TestMTPDrafter:
    def test_mtp_drafts_and_stays_lossless(self):
        """DeepSeek (MLA + MoE + cfg.mtp): the MTP head drafts a [B, k]
        chain (``spec_branch=1``) and greedy outputs stay identical to the
        plain engine (the untrained head drafts near-randomly;
        verification absorbs it)."""
        from repro.models import model as M
        from repro.serve.engine import ContinuousBatchingEngine
        cfg = ARCHS["deepseek-v3-671b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, int(l)).tolist()
                   for l in rng.integers(3, 12, size=4)]
        budgets = [int(b) for b in rng.integers(2, 7, size=4)]
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32,
            quantize=False).generate_all(prompts, budgets)
        eng = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32, quantize=False,
            spec_tree=3, spec_branch=1, drafter="mtp", chunk=4)
        assert eng.generate_all(prompts, budgets) == ref
        assert eng.stats["verify_steps"] > 0

    def test_mtp_draft_shape_and_determinism(self):
        from repro.models import model as M
        from repro.models.transformer import Runtime
        cfg = ARCHS["deepseek-v3-671b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        h = jnp.zeros((3, cfg.d_model))
        tok = jnp.array([1, 2, 3], jnp.int32)
        pos = jnp.array([4, 5, 6], jnp.int32)
        a = M.mtp_draft_tree(params, cfg, h, tok, pos, 4, 1, Runtime())
        b = M.mtp_draft_tree(params, cfg, h, tok, pos, 4, 1, Runtime())
        assert a.shape == (3, 4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert (np.asarray(a) >= 0).all() and \
            (np.asarray(a) < cfg.vocab_size).all()

    def test_mtp_tree_drafts_and_stays_lossless(self):
        """The MTP beam (tree lane, drafter='mtp'): top-branch first tokens
        each root a greedy chain; greedy outputs stay identical to the
        plain engine."""
        from repro.models import model as M
        from repro.serve.engine import ContinuousBatchingEngine
        cfg = ARCHS["deepseek-v3-671b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, int(l)).tolist()
                   for l in rng.integers(3, 12, size=4)]
        budgets = [int(b) for b in rng.integers(2, 7, size=4)]
        ref = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32,
            quantize=False).generate_all(prompts, budgets)
        eng = ContinuousBatchingEngine(
            cfg, params, n_slots=2, max_len=32, quantize=False,
            spec_tree=3, spec_branch=2, drafter="mtp", chunk=4)
        assert eng.generate_all(prompts, budgets) == ref
        assert eng.stats["verify_steps"] > 0
        assert len(eng.stats["spec_accept_hist"]) == 4

    def test_mtp_draft_tree_shape_and_branch1_equals_chain(self):
        """mtp_draft_tree returns [B, n] chain-major tokens, is
        deterministic, and at branch=1 is exactly the greedy recursion of
        the MTP head; the host-side parent pointers match the static
        topology."""
        from repro.models import model as M
        from repro.models import transformer as T
        from repro.models.transformer import Runtime, mtp_chain_lengths
        from repro.serve.drafter import MTPDrafter
        cfg = ARCHS["deepseek-v3-671b"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        rt = Runtime()
        h = jnp.zeros((3, cfg.d_model))
        tok = jnp.array([1, 2, 3], jnp.int32)
        pos = jnp.array([4, 5, 6], jnp.int32)
        a = M.mtp_draft_tree(params, cfg, h, tok, pos, 4, 2, rt)
        b = M.mtp_draft_tree(params, cfg, h, tok, pos, 4, 2, rt)
        assert a.shape == (3, 4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        chain = M.mtp_draft_tree(params, cfg, h, tok, pos, 4, 1, rt)
        hh, tt, greedy = h.astype(jnp.float32), tok, []
        for i in range(4):
            lg, hh = T._mtp_cell(params, cfg, hh, tt, pos + i, rt)
            tt = jnp.argmax(lg, -1).astype(jnp.int32)
            greedy.append(tt)
        np.testing.assert_array_equal(np.asarray(chain),
                                      np.asarray(jnp.stack(greedy, axis=1)))
        # drafter wrapper exposes the matching draft-space parents:
        # chains of lengths [2, 2] -> [-1, 0, -1, 2]
        d = MTPDrafter(cfg, rt, 4, 2)
        assert mtp_chain_lengths(4, 2) == [2, 2]
        assert d.tree_parents == [-1, 0, -1, 2]
        assert MTPDrafter(cfg, rt, 4, 1).tree_parents == chain_parents(4)

    def test_mtp_requires_mtp_head(self, gqa_setup):
        from repro.serve.engine import ContinuousBatchingEngine
        cfg, params = gqa_setup
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(cfg, params, n_slots=1, max_len=32,
                                     spec_tree=2, drafter="mtp")
