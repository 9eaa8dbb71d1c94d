"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels.pim_mvm import ref as pim_ref, ops as pim_ops
from repro.kernels.pim_mvm.kernel import pim_mvm_pallas
from repro.kernels.int8_matmul import ref as mm_ref, ops as mm_ops
from repro.kernels.decode_attn import ref as da_ref, ops as da_ops

jax.config.update("jax_platform_name", "cpu")


def _mk_linear(key, k, n, scale=0.3):
    w = jax.random.normal(key, (k, n)) * scale
    return quant.make_quantized_linear(w), w


class TestPimMvm:
    @pytest.mark.parametrize("m,k,n", [
        (1, 128, 512), (8, 256, 512), (16, 384, 1024),
        (3, 100, 130),            # non-aligned -> padding path
        (32, 1024, 256),
    ])
    def test_matches_oracle(self, m, k, n):
        kx, kw = jax.random.split(jax.random.key(m * k + n))
        x = jax.random.normal(kx, (m, k))
        lin, _ = _mk_linear(kw, k, n)
        x_q, x_s = quant.quantize_activation(x)
        hi, lo = quant.pack_qlc(lin.w_q)
        want = pim_ref.ref_int(x_q, hi, lo, x_s, lin.w_scale)
        got = pim_ops.pim_mvm(x_q, x_s, lin)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    def test_bitserial_oracle_exact_vs_int(self):
        """Eq. (2)'s bit-serial dataflow is integer-exact."""
        x = jax.random.randint(jax.random.key(0), (8, 64), -127, 128, jnp.int8)
        w = jax.random.randint(jax.random.key(1), (64, 32), -127, 128, jnp.int8)
        hi, lo = quant.pack_qlc(w)
        s1 = jnp.ones((8, 1)); s2 = jnp.ones((32,))
        a = pim_ref.ref_int(x, hi, lo, s1, s2)
        b = pim_ref.ref_bitserial(x, hi, lo, s1, s2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("bits", [4, 8])
    def test_bit_widths(self, bits):
        """Eq. (3): latency scales with B_input; math stays exact per width."""
        x_q = jax.random.randint(jax.random.key(2), (4, 128),
                                 -(2**(bits-1) - 1), 2**(bits-1), jnp.int8)
        w = jax.random.randint(jax.random.key(3), (128, 256), -127, 128, jnp.int8)
        hi, lo = quant.pack_qlc(w)
        xs = jnp.ones((4, 1)); ws = jnp.ones((256,))
        got = pim_mvm_pallas(x_q, xs, hi, lo, ws, bits=8)
        want = pim_ref.ref_int(x_q, hi, lo, xs, ws)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    def test_batched_leading_dims(self):
        x = jax.random.normal(jax.random.key(4), (2, 3, 256))
        lin, w = _mk_linear(jax.random.key(5), 256, 512)
        x_q, x_s = quant.quantize_activation(x)
        out = pim_ops.pim_mvm(x_q, x_s, lin)
        assert out.shape == (2, 3, 512)
        rel = jnp.abs(out - x @ w).max() / jnp.abs(x @ w).max()
        assert float(rel) < 0.05


class TestInt8Matmul:
    @pytest.mark.parametrize("m,k,n", [
        (1, 128, 128), (128, 512, 256), (7, 100, 50), (256, 1024, 640),
    ])
    def test_matches_oracle(self, m, k, n):
        kx, kw = jax.random.split(jax.random.key(m + k + n))
        x = jax.random.normal(kx, (m, k))
        lin, _ = _mk_linear(kw, k, n)
        x_q, x_s = quant.quantize_activation(x)
        want = mm_ref.ref(x_q, lin.w_q, x_s, lin.w_scale)
        got = mm_ops.int8_matmul(x_q, x_s, lin)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    def test_fused_equals_bitserial(self):
        """The optimized kernel computes exactly what the PIM array computes."""
        x = jax.random.normal(jax.random.key(6), (16, 256))
        lin, _ = _mk_linear(jax.random.key(7), 256, 512)
        x_q, x_s = quant.quantize_activation(x)
        a = mm_ops.int8_matmul(x_q, x_s, lin)
        b = pim_ops.pim_mvm(x_q, x_s, lin)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


class TestDecodeAttn:
    @pytest.mark.parametrize("b,s,g,rep,d,length", [
        (1, 256, 1, 1, 64, 256),
        (2, 1024, 4, 2, 64, 700),
        (2, 512, 2, 4, 128, 100),
        (1, 300, 8, 1, 64, 299),      # non-aligned seq
        (2, 1024, 2, 2, 64, 3),       # tiny length: tail blocks skipped
        (1, 2048, 1, 1, 64, 1),       # single live key, 3 of 4 blocks dead
    ])
    def test_matches_oracle(self, b, s, g, rep, d, length):
        k1, k2, k3 = jax.random.split(jax.random.key(b * s + g + d), 3)
        q = jax.random.normal(k1, (b, 1, g * rep, d))
        k = jax.random.normal(k2, (b, s, g, d))
        v = jax.random.normal(k3, (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        ln = jnp.array(length, jnp.int32)
        want = da_ref.ref(q, k_q, k_s, v_q, v_s, ln)
        got = da_ops.decode_attention(q, k_q, k_s, v_q, v_s, ln)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-6)

    def test_ragged_tiny_lengths_match_oracle(self):
        """Per-slot [B] lengths where only one row reaches past the first
        key block: the block-skip guard (`s_idx * bs < max(limits)`) must
        drop dead blocks per batch row without perturbing the long row."""
        b, s, g, d = 3, 1024, 2, 64
        q = jax.random.normal(jax.random.key(9), (b, 1, g, d))
        k = jax.random.normal(jax.random.key(10), (b, s, g, d))
        v = jax.random.normal(jax.random.key(11), (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        ln = jnp.array([2, 900, 1], jnp.int32)
        want = da_ref.ref(q, k_q, k_s, v_q, v_s, ln[:, None, None, None])
        got = da_ops.decode_attention(q, k_q, k_s, v_q, v_s, ln)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-6)

    def test_length_mask_excludes_tail(self):
        """Entries past `length` must not affect the output."""
        b, s, g, d = 1, 128, 2, 64
        q = jax.random.normal(jax.random.key(0), (b, 1, g, d))
        k = jax.random.normal(jax.random.key(1), (b, s, g, d))
        v = jax.random.normal(jax.random.key(2), (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        o1 = da_ops.decode_attention(q, k_q, k_s, v_q, v_s, jnp.array(64))
        # poison the tail
        k_q2 = k_q.at[:, 64:].set(127)
        v_q2 = v_q.at[:, 64:].set(-127)
        o2 = da_ops.decode_attention(q, k_q2, k_s, v_q2, v_s, jnp.array(64))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)


def _chain_anc(b, t):
    """Chain ancestor masks: node i's ancestors are 0..i."""
    row = (1 << (np.arange(t, dtype=np.int64) + 1)) - 1
    return jnp.asarray(np.tile(row.astype(np.int32), (b, 1)))


def _tree_anc(parents):
    """Ancestor bitmasks from a parent-pointer list (parents[0] == -1)."""
    anc = np.zeros(len(parents), np.int64)
    for i, p in enumerate(parents):
        anc[i] = (anc[p] if p >= 0 else 0) | (1 << i)
    return jnp.asarray(anc.astype(np.int32)[None, :])


class TestVerifyTreeAttn:
    """Verify kernel (speculative decode): T query tokens per slot, each a
    node of a draft tree masked to the committed prefix plus its
    ancestor-or-self in-window keys; a chain gives the stepped causal mask
    (query t sees keys [0, pos+t])."""

    @pytest.mark.parametrize("b,s,g,rep,d,t,topology", [
        (2, 256, 2, 2, 64, 4, "tree"),
        (1, 300, 4, 1, 64, 7, "tree"),      # non-aligned seq
        (3, 512, 1, 4, 128, 5, "tree"),
        (2, 128, 2, 2, 64, 1, "tree"),      # root-only window
        (2, 256, 2, 2, 64, 4, "chain"),
        (1, 300, 4, 1, 64, 5, "chain"),     # non-aligned seq
        (3, 512, 1, 4, 128, 3, "chain"),
        (2, 128, 2, 2, 64, 1, "chain"),     # T=1 degenerates to plain decode
    ])
    def test_matches_oracle(self, b, s, g, rep, d, t, topology):
        k1, k2, k3 = jax.random.split(jax.random.key(b * s + g + d + t), 3)
        q = jax.random.normal(k1, (b, t, g * rep, d))
        k = jax.random.normal(k2, (b, s, g, d))
        v = jax.random.normal(k3, (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        pos = jnp.asarray(np.arange(b) * 7 + s // 2, jnp.int32)
        if topology == "chain":
            anc = _chain_anc(b, t)
        else:
            # per-slot random trees (parents[i] < i), seeded by the shape
            rng = np.random.RandomState(b * s + t)
            anc = np.zeros((b, t), np.int64)
            for bb in range(b):
                par = [-1] + [rng.randint(0, i) for i in range(1, t)]
                anc[bb] = np.asarray(_tree_anc(par))[0]
            anc = jnp.asarray(anc.astype(np.int32))
        want = da_ref.verify_tree_ref(q, k_q, k_s, v_q, v_s, pos, anc)
        got = da_ops.verify_attention_tree(q, k_q, k_s, v_q, v_s, pos, anc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-6)

    def test_t1_equals_decode_attention(self):
        """The verify kernel at T=1 (a root-only chain) is the decode
        kernel."""
        b, s, g, d = 2, 128, 2, 64
        q = jax.random.normal(jax.random.key(3), (b, 1, g, d))
        k = jax.random.normal(jax.random.key(4), (b, s, g, d))
        v = jax.random.normal(jax.random.key(5), (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        ln = jnp.array([40, 90], jnp.int32)
        a = da_ops.decode_attention(q, k_q, k_s, v_q, v_s, ln)
        # decode masks keys < length; the root sees keys < pos + 1
        b_ = da_ops.verify_attention_tree(q, k_q, k_s, v_q, v_s, ln - 1,
                                          _chain_anc(b, 1))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-6)

    def test_stepped_mask_excludes_future_rows(self):
        """Poisoning rows past each query's own limit (pos + t) must not
        change that query's output — a chain's per-row stepped causal
        mask."""
        b, s, g, d, t = 1, 128, 2, 64, 3
        q = jax.random.normal(jax.random.key(6), (b, t, g, d))
        k = jax.random.normal(jax.random.key(7), (b, s, g, d))
        v = jax.random.normal(jax.random.key(8), (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        pos = jnp.array([50], jnp.int32)
        anc = _chain_anc(b, t)
        o1 = da_ops.verify_attention_tree(q, k_q, k_s, v_q, v_s, pos, anc)
        for qt in range(t):
            lim = 50 + qt + 1
            k_q2 = k_q.at[:, lim:].set(127)
            v_q2 = v_q.at[:, lim:].set(-127)
            o2 = da_ops.verify_attention_tree(q, k_q2, k_s, v_q2, v_s, pos,
                                              anc)
            np.testing.assert_allclose(np.asarray(o1[:, qt]),
                                       np.asarray(o2[:, qt]), rtol=1e-6)

    def test_chain_window_equals_sequential_decode(self):
        """A chain window of T nodes at ``pos`` equals T sequential
        ``decode_attention`` calls, call t at cache length pos + t + 1."""
        b, s, g, d, t = 2, 256, 2, 64, 4
        q = jax.random.normal(jax.random.key(12), (b, t, g, d))
        k = jax.random.normal(jax.random.key(13), (b, s, g, d))
        v = jax.random.normal(jax.random.key(14), (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        pos = jnp.array([30, 100], jnp.int32)
        win = da_ops.verify_attention_tree(q, k_q, k_s, v_q, v_s, pos,
                                           _chain_anc(b, t))
        for qt in range(t):
            seq = da_ops.decode_attention(q[:, qt:qt + 1], k_q, k_s, v_q,
                                          v_s, pos + qt + 1)
            np.testing.assert_allclose(np.asarray(win[:, qt:qt + 1]),
                                       np.asarray(seq), rtol=1e-6)

    def test_non_ancestor_rows_masked(self):
        """Poisoning the K/V rows of non-ancestor nodes (siblings and the
        uncommitted tail) must not change a node's output."""
        b, s, g, d = 1, 128, 2, 64
        # root -> {1, 2}; 1 -> 3; 2 -> 4   (two branches of depth 2)
        parents = [-1, 0, 0, 1, 2]
        t = len(parents)
        anc = _tree_anc(parents)
        q = jax.random.normal(jax.random.key(15), (b, t, g, d))
        k = jax.random.normal(jax.random.key(16), (b, s, g, d))
        v = jax.random.normal(jax.random.key(17), (b, s, g, d))
        k_q, k_s = quant.quantize_kv(k)
        v_q, v_s = quant.quantize_kv(v)
        pos = jnp.array([40], jnp.int32)
        o1 = da_ops.verify_attention_tree(q, k_q, k_s, v_q, v_s, pos, anc)
        for node in range(t):
            a = int(np.asarray(anc)[0, node])
            dead = [j for j in range(t) if not (a >> j) & 1]
            k_q2, v_q2 = k_q, v_q
            for j in dead:
                k_q2 = k_q2.at[:, 40 + j].set(127)
                v_q2 = v_q2.at[:, 40 + j].set(-127)
            k_q2 = k_q2.at[:, 40 + t:].set(127)   # uncommitted tail too
            v_q2 = v_q2.at[:, 40 + t:].set(-127)
            o2 = da_ops.verify_attention_tree(q, k_q2, k_s, v_q2, v_s,
                                              pos, anc)
            np.testing.assert_allclose(np.asarray(o1[:, node]),
                                       np.asarray(o2[:, node]), rtol=1e-6)
