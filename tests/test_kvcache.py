"""Slotted SLC-region KV cache: per-slot-length append/free round-trips and
the cache_bytes-invariance-under-churn property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvcache as KV

jax.config.update("jax_platform_name", "cpu")

L, B, S, H, D = 2, 3, 16, 2, 8


def _kv(key, t=1):
    k1, k2 = jax.random.split(jax.random.key(key))
    return (jax.random.normal(k1, (B, t, H, D)),
            jax.random.normal(k2, (B, t, H, D)))


class TestSlottedAppend:
    def test_heterogeneous_append_lands_per_slot(self):
        cache = KV.init_cache(L, B, S, H, D)
        k, v = _kv(0)
        pos = jnp.array([0, 5, 11], jnp.int32)
        cache = KV.append_layer(cache, 0, k, v, pos)
        k_q, _ = KV.quantize_rows(k)
        for b, p in enumerate([0, 5, 11]):
            np.testing.assert_array_equal(
                np.asarray(cache.k_q[0, b, p]), np.asarray(k_q[b, 0]))
        # untouched rows stay zero
        assert int(jnp.abs(cache.k_q[0, 0, 1:]).max()) == 0
        assert int(jnp.abs(cache.k_q[1]).max()) == 0      # other layer

    def test_scalar_pos_matches_vector_pos(self):
        """The aligned single-batch path is the equal-entries special case."""
        k, v = _kv(1)
        c1 = KV.append_layer(KV.init_cache(L, B, S, H, D), 1, k, v, 3)
        c2 = KV.append_layer(KV.init_cache(L, B, S, H, D), 1, k, v,
                             jnp.full((B,), 3, jnp.int32))
        for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_append_free_roundtrip(self):
        cache = KV.init_cache(L, B, S, H, D)
        cache = KV.alloc_slot(cache, 1, 4)
        k, v = _kv(2)
        cache = KV.append_layer(cache, 0, k, v, cache.lengths)
        cache = KV.bump_length(cache, jnp.array([0, 1, 0], jnp.int32))
        assert cache.lengths.tolist() == [0, 5, 0]
        cache = KV.free_slot(cache, 1)
        assert cache.lengths.tolist() == [0, 0, 0]
        # stale rows survive until overwritten (write-in-place, no erase)
        assert int(jnp.abs(cache.k_q[0, 1, 4]).max()) > 0
        k2, v2 = _kv(3)
        cache = KV.append_layer(cache, 0, k2, v2, cache.lengths)
        np.testing.assert_array_equal(
            np.asarray(cache.k_q[0, 1, 0]),
            np.asarray(KV.quantize_rows(k2)[0][1, 0]))

    def test_split_heads_inverts_merge_heads(self):
        """The one conversion between per-head K/V and the cache's
        lane-dense rows: ``merge_heads`` is a reshape of the same bytes,
        and ``split_heads`` gives them back per head."""
        k, _ = _kv(8, t=3)
        rows = KV.merge_heads(k)
        assert rows.shape == (B, 3, H * D)
        np.testing.assert_array_equal(np.asarray(rows[:, :, D:2 * D]),
                                      np.asarray(k[:, :, 1]))
        np.testing.assert_array_equal(np.asarray(KV.split_heads(rows, H)),
                                      np.asarray(k))

    @pytest.mark.parametrize("s", [1, 2, S])
    def test_append_rows_matches_batched_update(self, s):
        """The one-row append into the lane-dense pool ([L, B, S, H·D]) at a
        traced layer lands exactly the bytes ``batched_update`` lands in a
        per-head [B, S, H, D] layer, clamped rows included, and leaves every
        other byte as it was; the [L, B, S, H, 1] scales likewise."""
        from repro.core.quant import quantize_kv
        k_q, k_s = quantize_kv(_kv(5)[0])
        pool = jax.random.randint(jax.random.key(6), (L, B, s, H, D),
                                  -127, 128, jnp.int32).astype(jnp.int8)
        scales = jax.random.uniform(jax.random.key(7), (L, B, s, H, 1))
        pos = jnp.array([0, s - 1, s + 3], jnp.int32)
        append = jax.jit(KV.append_rows)
        got = append(KV.merge_heads(pool), jnp.int32(1), KV.merge_heads(k_q),
                     pos)
        want = pool.at[1].set(KV.batched_update(pool[1], k_q, pos))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(KV.merge_heads(want)))
        np.testing.assert_array_equal(
            np.asarray(append(scales, jnp.int32(1), k_s, pos)),
            np.asarray(scales.at[1].set(
                KV.batched_update(scales[1], k_s, pos))))

    def test_multi_token_append(self):
        """Prefill-style appends (T>1) land contiguously from each slot pos."""
        cache = KV.init_cache(L, B, S, H, D)
        k, v = _kv(4, t=3)
        pos = jnp.array([2, 0, 7], jnp.int32)
        cache = KV.append_layer(cache, 0, k, v, pos)
        v_q, _ = KV.quantize_rows(v)
        for b, p in enumerate([2, 0, 7]):
            np.testing.assert_array_equal(
                np.asarray(cache.v_q[0, b, p:p + 3]), np.asarray(v_q[b]))


class TestLatentCache:
    def test_heterogeneous_latent_append(self):
        cache = KV.init_latent_cache(L, B, S, dim=6)
        c = jax.random.normal(jax.random.key(7), (B, 1, 6))
        pos = jnp.array([1, 9, 4], jnp.int32)
        cache = KV.append_latent(cache, 1, c, pos)
        got = (cache.c_q[1].astype(jnp.float32) * cache.c_s[1])
        for b, p in enumerate([1, 9, 4]):
            np.testing.assert_allclose(np.asarray(got[b, p]),
                                       np.asarray(c[b, 0]),
                                       rtol=0.05, atol=0.02)


class TestCacheBytesInvariance:
    def test_invariant_under_slot_churn(self):
        """Allocation, ragged appends, frees, and re-allocation never change
        the SLC footprint — slots are rows of a fixed pool, not allocations."""
        cache = KV.init_cache(L, B, S, H, D)
        baseline = KV.cache_bytes(cache)
        rng = np.random.default_rng(0)
        for step in range(30):
            op = step % 3
            if op == 0:
                cache = KV.alloc_slot(cache, int(rng.integers(B)),
                                      int(rng.integers(S // 2)))
            elif op == 1:
                k, v = _kv(step)
                cache = KV.append_layer(
                    cache, int(rng.integers(L)), k, v,
                    jnp.minimum(cache.lengths, S - 1))
            else:
                cache = KV.free_slot(cache, int(rng.integers(B)))
            assert KV.cache_bytes(cache) == baseline

    def test_property_hypothesis(self):
        pytest.importorskip("hypothesis", reason="property tests need "
                            "hypothesis (pip install .[test])")
        from hypothesis import given, settings, strategies as st

        @settings(deadline=None, max_examples=25)
        @given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, B - 1),
                                  st.integers(0, S - 1)), max_size=12))
        def prop(ops):
            cache = KV.init_cache(1, B, S, H, D)
            base = KV.cache_bytes(cache)
            for op, slot, n in ops:
                if op == 0:
                    cache = KV.alloc_slot(cache, slot, n)
                elif op == 1:
                    cache = KV.free_slot(cache, slot)
                else:
                    k, v = _kv(n)
                    cache = KV.append_layer(cache, 0, k, v, cache.lengths)
                assert KV.cache_bytes(cache) == base
                assert cache.k_q.shape == (1, B, S, H * D)

        prop()


class TestChunkedAppend:
    """Offset appends into a slot row — the chunked-prefill write primitive."""

    def test_chunk_update_lands_at_offset(self):
        buf = jnp.zeros((1, S, H, D))
        new = jax.random.normal(jax.random.key(3), (1, 4, H, D))
        out = KV.chunk_update(buf, new, 5)
        np.testing.assert_allclose(np.asarray(out[0, 5:9]), np.asarray(new[0]))
        assert float(jnp.abs(out[0, :5]).max()) == 0.0
        assert float(jnp.abs(out[0, 9:]).max()) == 0.0

    def test_chunk_update_traced_offset_single_compile(self):
        """One compiled update serves every cursor (traced start)."""
        f = jax.jit(KV.chunk_update)
        buf = jnp.zeros((1, S, H, D))
        new = jax.random.normal(jax.random.key(4), (1, 3, H, D))
        for start in (0, 4, 9):
            out = f(buf, new, jnp.int32(start))
            np.testing.assert_allclose(
                np.asarray(out[0, start:start + 3]), np.asarray(new[0]))

    def test_sequential_chunks_equal_one_shot_append(self):
        """Two chunked appends reproduce a single full-width write —
        per-token int8 quantization is chunking-invariant."""
        k, v = _kv(5, t=8)
        one = KV.append_layer(KV.init_cache(L, B, S, H, D), 0, k, v, 0)
        two = KV.init_cache(L, B, S, H, D)
        two = KV.append_layer_chunk(two, 0, k[:, :3], v[:, :3], 0)
        two = KV.append_layer_chunk(two, 0, k[:, 3:], v[:, 3:], 3)
        for name in ("k_q", "k_s", "v_q", "v_s"):
            np.testing.assert_array_equal(np.asarray(getattr(one, name)),
                                          np.asarray(getattr(two, name)))


class TestPrefixCopy:
    """Row-range copy between slots — the prefix-cache admission gather."""

    def test_copy_lands_prefix_and_preserves_tail(self):
        cache = KV.init_cache(L, B, S, H, D)
        k, v = _kv(11, t=8)
        cache = KV.append_layer(cache, 0, k, v, 0)       # rows 0..8, all slots
        k2, v2 = _kv(12, t=3)
        cache = KV.append_layer(cache, 1, k2, v2, 0)
        before = np.asarray(cache.k_q[0, 2])
        out = KV.copy_prefix(cache, 0, 2, 5)
        for name in ("k_q", "k_s", "v_q", "v_s"):
            got = np.asarray(getattr(out, name))
            np.testing.assert_array_equal(got[:, 2, :5], got[:, 0, :5])
        # rows at/past n keep dst's dead in-place entries (no erase)
        np.testing.assert_array_equal(np.asarray(out.k_q[0, 2, 5:]),
                                      before[5:])
        assert out.lengths.tolist() == [0, 0, 5]

    def test_traced_args_single_compile(self):
        """One compiled gather serves every (src, dst, n) triple."""
        f = jax.jit(KV.copy_prefix)
        cache = KV.init_cache(L, B, S, H, D)
        k, v = _kv(13, t=6)
        cache = KV.append_layer(cache, 0, k, v, 0)
        for src, dst, n in ((0, 1, 3), (1, 2, 6), (2, 0, 1)):
            out = f(cache, jnp.int32(src), jnp.int32(dst), jnp.int32(n))
            np.testing.assert_array_equal(np.asarray(out.k_q[0, dst, :n]),
                                          np.asarray(cache.k_q[0, src, :n]))
            assert int(out.lengths[dst]) == n


class TestZeroRowEdges:
    """n == 0 degenerate copies: no payload moves, no size-0 gather traces."""

    def test_copy_prefix_zero_rows(self):
        cache = KV.init_cache(L, B, S, H, D)
        k, v = _kv(21, t=4)
        cache = KV.append_layer(cache, 0, k, v, 0)
        before = np.asarray(cache.k_q[0, 2])
        out = KV.copy_prefix(cache, 0, 2, 0)
        np.testing.assert_array_equal(np.asarray(out.k_q[0, 2]), before)
        assert out.lengths.tolist() == [0, 0, 0]

    def test_path_gather_zero_width_window(self):
        """A [B, 0] selector is the W==0 static edge: identity, even under
        jit (the guard keeps the trace free of size-0 take_along_axis)."""
        buf = jax.random.normal(jax.random.key(22), (L, B, S, H, D))
        base = jnp.array([0, 3, 7], jnp.int32)
        sel = jnp.zeros((B, 0), jnp.int32)
        keep = jnp.zeros((B,), jnp.int32)
        for f in (KV.path_gather, jax.jit(KV.path_gather)):
            np.testing.assert_array_equal(
                np.asarray(f(buf, base, sel, keep)), np.asarray(buf))

    def test_copy_slot_prefix_zero_rows(self):
        """Engine-level gather with n=0 (empty prefix match): every leaf's
        dst rows keep their dead entries and only pos[dst] lands at 0."""
        from repro.models.transformer import copy_slot_prefix
        key = jax.random.key(23)
        leaf = jax.random.normal(key, (2, B, S, H, D))
        state = {"groups": [(leaf, leaf * 2)],
                 "pos": jnp.array([4, 6, 2], jnp.int32)}
        out = copy_slot_prefix(state, jnp.int32(0), jnp.int32(2), jnp.int32(0))
        for got, want in zip(jax.tree.leaves(out["groups"]),
                             jax.tree.leaves(state["groups"])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert out["pos"].tolist() == [4, 6, 0]


def _lane_dense_case(b, rep, d, seed, t=1):
    """q [b, t, G·rep, d] and a random int8 cache of S rows, per head
    ([b, S, G, d]) and lane-dense ([b, S, G·d]), with its scales."""
    from repro.core.quant import quantize_kv
    g = 2 if rep > 1 else 4
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k1, (b, t, g * rep, d))
    k_q, k_s = quantize_kv(jax.random.normal(k2, (b, S, g, d)))
    v_q, v_s = quantize_kv(jax.random.normal(k3, (b, S, g, d)))
    flat = KV.merge_heads
    return q, (k_q, k_s, v_q, v_s), (flat(k_q), k_s, flat(v_q), v_s)


LANE_DENSE = pytest.mark.parametrize("rep,d", [(1, 24), (4, 16)],
                                     ids=["mha-hd24", "gqa-rep4"])


class TestLaneDenseAttention:
    """Decode and verify attention read the lane-dense pool as it lies and
    match the per-head dMVM oracle (``kernels/decode_attn/ref.py``)."""

    @pytest.mark.parametrize("backend", ["dense", "fused_int8"])
    @pytest.mark.parametrize("b", [1, 4])
    @LANE_DENSE
    def test_decode_matches_per_head_oracle(self, rep, d, b, backend):
        from repro.kernels.decode_attn import ref as da_ref
        from repro.models import attention as A
        q, per_head, flat = _lane_dense_case(b, rep, d, seed=b + rep)
        # one slot's cursor at max_len - 1: it attends every row
        length = jnp.array([S, 5, 1, 11][:b], jnp.int32)
        want = da_ref.ref(q, *per_head, length[:, None, None, None])
        got = A.decode_attention_int8(q, *flat, length, backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("anc", [[1, 3, 7], [1, 3, 5]],
                             ids=["chain", "tree"])
    @LANE_DENSE
    def test_verify_matches_per_head_oracle(self, rep, d, anc):
        from repro.kernels.decode_attn import ref as da_ref
        from repro.models import attention as A
        t = 3
        q, per_head, flat = _lane_dense_case(4, rep, d, seed=rep, t=t)
        pos = jnp.array([S - t, 0, 6, 9], jnp.int32)
        anc = jnp.broadcast_to(jnp.array(anc, jnp.int32), (4, t))
        want = da_ref.verify_tree_ref(q, *per_head, pos, anc)
        got = A.verify_attention_int8(q, *flat, pos, anc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestSlotLedger:
    """Host-side refcounts over pool slots (prefix-cache holds)."""

    def test_lifecycle(self):
        led = KV.SlotLedger()
        assert led.count(3) == 0
        assert led.incref(3) == 1            # leaf claim
        assert led.incref(3) == 2            # alias writer
        assert led.held() == {3}
        assert led.decref(3) == 1            # writer released (cancel)
        assert led.decref(3) == 0            # leaf evicted
        assert led.held() == set()

    def test_double_free_raises(self):
        led = KV.SlotLedger()
        led.incref(1)
        led.decref(1)
        with pytest.raises(RuntimeError, match="double free"):
            led.decref(1)

    def test_release_without_hold_raises(self):
        with pytest.raises(RuntimeError):
            KV.SlotLedger().decref(0)

    def test_randomized_claim_storm(self):
        """Property test over mixed publish/alias/cancel/preempt/evict
        storms: the ledger must track a shadow refcount map exactly —
        ``held()`` is always the live-claim set, counts never go negative,
        and every release below zero raises instead of corrupting."""
        rng = np.random.default_rng(17)
        led = KV.SlotLedger()
        shadow: dict[int, int] = {}
        for _ in range(2000):
            slot = int(rng.integers(0, 8))
            have = shadow.get(slot, 0)
            op = rng.choice(["publish", "alias", "release", "bad_release"])
            if op in ("publish", "alias"):          # leaf claim / alias writer
                assert led.incref(slot) == have + 1
                shadow[slot] = have + 1
            elif op == "release" and have:          # cancel / preempt / evict
                assert led.decref(slot) == have - 1
                if have == 1:
                    del shadow[slot]
                else:
                    shadow[slot] = have - 1
            elif op == "bad_release" and not have:  # double free must raise
                with pytest.raises(RuntimeError):
                    led.decref(slot)
            assert led.count(slot) == shadow.get(slot, 0)
            assert led.held() == set(shadow)


class TestSpeculativeRollback:
    def test_rewind_then_overwrite_equals_straight_append(self):
        """The speculative verify pattern: append a k+1-token window at the
        per-slot cursor, rewind lengths to the accepted prefix, then let
        the next append overwrite the dead rows in place — the cache must
        equal one that only ever appended the committed tokens."""
        k, v = _kv(7, t=4)
        k2, v2 = _kv(8, t=4)
        pos = jnp.array([0, 3, 6], jnp.int32)
        # speculative: 4-token window, only 2 accepted per slot
        spec = KV.alloc_slot(KV.init_cache(L, B, S, H, D),
                             jnp.arange(B), pos)
        spec = KV.append_layer(spec, 0, k, v, pos)
        spec = KV.rewind_lengths(spec, pos + 2)          # rollback, no erase
        np.testing.assert_array_equal(np.asarray(spec.lengths),
                                      np.asarray(pos) + 2)
        # next window starts at the committed cursor, overwriting dead rows
        spec = KV.append_layer(spec, 0, k2, v2, spec.lengths)
        # straight: only the committed tokens ever appended
        ref = KV.append_layer(KV.init_cache(L, B, S, H, D), 0,
                              k[:, :2], v[:, :2], pos)
        ref = KV.append_layer(ref, 0, k2, v2, pos + 2)
        for b in range(B):
            p = int(pos[b])
            np.testing.assert_array_equal(
                np.asarray(spec.k_q[0, b, :p + 6]),
                np.asarray(ref.k_q[0, b, :p + 6]))
            np.testing.assert_array_equal(
                np.asarray(spec.v_q[0, b, :p + 6]),
                np.asarray(ref.v_q[0, b, :p + 6]))
