"""Multi-device tests (subprocess with forced host devices) + dry-run
artifact integration checks."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
ART = ROOT / "artifacts" / "dryrun"
MANIFEST = ART / "quick_manifest.json"


# every snippet gets the repo's Auto-axis make_mesh and a shard_map with
# replication checking off (the collectives under test are hand-written)
_PRELUDE = """\
import jax
from repro.launch.mesh import make_mesh


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
"""


def _run_with_devices(n: int, code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c",
                        _PRELUDE + textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


class TestCollectives:
    def test_htree_allreduce_equals_psum(self):
        out = _run_with_devices(8, """
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.dist.collectives import htree_allreduce
            mesh = make_mesh((8,), ("model",))
            x = jnp.arange(32.0).reshape(8, 4)
            def f(x):
                return htree_allreduce(x, "model")
            def g(x):
                return jax.lax.psum(x, "model")
            a = shard_map(f, mesh, P("model", None), P("model", None))(x)
            b = shard_map(g, mesh, P("model", None), P("model", None))(x)
            import numpy as np
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
            print("HTREE_OK")
        """)
        assert "HTREE_OK" in out

    def test_moe_shard_map_matches_local(self):
        """EP shard_map MoE == single-device MoE on identical inputs."""
        out = _run_with_devices(8, """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import moe as MoE
            from repro.models.transformer import _moe_block, Runtime
            cfg = ARCHS["grok-1-314b"].reduced()   # E=4 experts (reduced)
            p = MoE.moe_init(jax.random.key(0), cfg)
            x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
            ref, _ = MoE.moe_apply(p, x, cfg, axis_name=None)
            mesh = make_mesh((2, 4), ("data", "model"))
            rt = Runtime(mesh=mesh, data_axes=("data",))
            got, _ = jax.jit(lambda pp, xx: _moe_block(pp, xx, cfg, rt))(p, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-3, atol=2e-4)
            print("MOE_OK")
        """)
        assert "MOE_OK" in out

    def test_sharded_train_step_matches_single_device(self):
        out = _run_with_devices(8, """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.registry import ARCHS
            from repro.configs.shapes import ShapeConfig
            from repro.data.pipeline import SyntheticTokens
            from repro.dist import sharding as SH
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.optim.adamw import AdamW
            from repro.train.train_step import make_train_step
            cfg = ARCHS["llama3-8b"].reduced()
            shape = ShapeConfig("tiny", 16, 8, "train")
            batch = SyntheticTokens(cfg, shape, seed=5).batch_at(0)
            params = M.init_params(jax.random.key(0), cfg)
            opt = AdamW(lr=1e-3, warmup_steps=1, total_steps=10)
            # single device
            s0 = jax.jit(make_train_step(cfg, Runtime(), opt))
            p0, _, m0 = s0(params, opt.init(params), batch)
            # 2x4 mesh with real shardings
            mesh = make_mesh((2, 4), ("data", "model"))
            rt = Runtime(mesh=mesh, data_axes=("data",))
            psh = SH.param_shardings(cfg, jax.eval_shape(lambda: params), mesh)
            params_sharded = jax.device_put(params, psh)
            s1 = jax.jit(make_train_step(cfg, rt, opt))
            p1, _, m1 = s1(params_sharded, opt.init(params_sharded), batch)
            assert abs(float(m0["loss"]) - float(m1["loss"])) < 5e-3, (m0, m1)
            d = max(float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max())
                    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
            assert d < 5e-3, d
            print("TRAIN_MATCH_OK")
        """)
        assert "TRAIN_MATCH_OK" in out


class TestHtreeProperty:
    """The tree all-reduce must equal psum off the 8-leaf happy path: ragged
    axis sizes (non-power-of-two trees pad their last level) and odd
    trailing shapes."""

    @pytest.mark.parametrize("n", [3, 5, 6])
    def test_ragged_axis_sizes(self, n):
        out = _run_with_devices(n, f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.dist.collectives import htree_allreduce
            n = {n}
            mesh = make_mesh((n,), ("model",))
            for shape in [(n, 7), (n, 5, 3), (n, 1), (n, 2, 3, 5)]:
                x = (jax.random.normal(jax.random.key(shape[-1]), shape)
                     * 100.0).astype(jnp.float32)
                spec = P(*("model",) + (None,) * (len(shape) - 1))
                a = shard_map(lambda v: htree_allreduce(v, "model"),
                              mesh, spec, spec)(x)
                b = shard_map(lambda v: jax.lax.psum(v, "model"),
                              mesh, spec, spec)(x)
                # tree vs ring reassociation: equal up to fp32 ulps
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5)
            print("HTREE_RAGGED_OK")
        """)
        assert "HTREE_RAGGED_OK" in out

    def test_round_count_matches_latency_model(self):
        """The collective must issue exactly tree_depth(n) up-sweep rounds
        plus tree_depth(n) down-sweep rounds (one ppermute each) — the
        round count core/htree.py charges as ``depth * level_lat``."""
        out = _run_with_devices(8, """
            import numpy as np
            import jax, jax.numpy as jnp
            from jax.sharding import Mesh, PartitionSpec as P
            from repro.core.htree import tree_depth
            from repro.dist.collectives import htree_allreduce
            for n in (2, 3, 5, 6, 8):
                mesh = Mesh(np.asarray(jax.devices()[:n]), ("model",))
                f = shard_map(lambda v: htree_allreduce(v, "model"),
                              mesh, P("model"), P("model"))
                jaxpr = str(jax.make_jaxpr(f)(jnp.zeros((n,))))
                rounds = jaxpr.count("ppermute")
                assert rounds == 2 * tree_depth(n), (n, rounds, jaxpr)
            print("ROUNDS_OK")
        """)
        assert "ROUNDS_OK" in out


class TestDryRunArtifacts:
    """Schema checks over artifacts/dryrun records.  CI seeds them with
    ``dryrun --quick`` (manifest present); a full ``--all --both-meshes``
    sweep is validated against the production thresholds."""

    def _records(self):
        return [json.loads(p.read_text()) for p in ART.glob("*.json")
                if p.name != MANIFEST.name]

    def test_all_cells_ok_or_documented_skip(self):
        if not ART.exists():
            pytest.skip("dry-run artifacts not generated "
                        "(run: python -m repro.launch.dryrun --quick)")
        recs = self._records()
        if MANIFEST.exists():
            manifest = json.loads(MANIFEST.read_text())
            missing = [n for n in manifest["artifacts"]
                       if not (ART / n).exists()]
            assert not missing, missing
            assert len(recs) >= len(manifest["artifacts"])
        else:
            assert len(recs) >= 80, "expected 40 cells x 2 meshes"
        bad = [r for r in recs if r["status"] not in ("ok", "skipped")]
        assert not bad, [(b["arch"], b["shape"], b.get("error")) for b in bad]
        skips = [r for r in recs if r["status"] == "skipped"]
        assert all("sub-quadratic" in r["reason"] for r in skips)

    def test_ok_records_have_cost_and_collectives(self):
        if not ART.exists():
            pytest.skip("dry-run artifacts not generated")
        ok = [r for r in self._records() if r["status"] == "ok"]
        if not ok:
            pytest.skip("no ok records")
        for r in ok:
            assert r["cost"]["flops"] > 0, (r["arch"], r["shape"])
            assert "total" in r["collectives"], (r["arch"], r["shape"])
            assert r["n_devices"] >= 8, (r["arch"], r["shape"])

    def test_multi_pod_coverage(self):
        recs = [json.loads(p.read_text()) for p in ART.glob("*pod2x16x16*.json")]
        if not recs:
            pytest.skip("multi-pod artifacts not generated (full sweep only)")
        ok = [r for r in recs if r["status"] == "ok"]
        assert len(ok) >= 32
        assert all(r["n_devices"] == 512 for r in ok)


@pytest.mark.skipif(not list(ART.glob("*__opt.json")), reason="variant artifacts absent")
class TestPerfVariants:
    """SecPerf: the optimized variants must beat the paper-faithful baseline
    on their targeted roofline term (same accounting ruler)."""

    def _load(self, name):
        return json.loads((ART / name).read_text())

    def test_resident_moe_cuts_collectives(self):
        for arch in ("jamba-1.5-large-398b", "deepseek-v3-671b"):
            base = self._load(f"{arch}__decode_32k__pod16x16.json")
            opt = self._load(f"{arch}__decode_32k__pod16x16__opt.json")
            cb = base["collectives_corrected"]["total"]
            co = opt["collectives_corrected"]["total"]
            assert co < 0.25 * cb, (arch, cb, co)

    def test_opt_memory_not_worse(self):
        for arch in ("jamba-1.5-large-398b", "deepseek-v3-671b", "llama3-8b"):
            base = self._load(f"{arch}__decode_32k__pod16x16.json")
            opt = self._load(f"{arch}__decode_32k__pod16x16__opt.json")
            assert (opt["cost_corrected"]["bytes_accessed"]
                    <= 1.02 * base["cost_corrected"]["bytes_accessed"])


class TestResidentMoE:
    """Serve-resident expert layouts must be numerically identical to the
    single-device MoE (they only change where weights live)."""

    @pytest.mark.parametrize("mesh_shape,axes", [
        ((2, 4), ("data", "model")),    # ep_data for reduced grok (E=4)
        ((8, 1), ("data", "model")),    # etp2 (E=4 % dp 8 != 0; ff % 8 == 0)
    ])
    def test_resident_matches_local(self, mesh_shape, axes):
        out = _run_with_devices(8, f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import moe as MoE
            from repro.models.transformer import _moe_block, Runtime
            from repro.dist import sharding as SH
            cfg = ARCHS["grok-1-314b"].reduced()
            p = MoE.moe_init(jax.random.key(0), cfg)
            x = jax.random.normal(jax.random.key(1), (8, 4, cfg.d_model))
            ref, _ = MoE.moe_apply(p, x, cfg, axis_name=None)
            mesh = make_mesh({mesh_shape}, {axes})
            strat = SH.moe_serve_strategy(cfg, mesh)
            rt = Runtime(mesh=mesh, data_axes=("data",),
                         serve_resident_moe=True)
            got, _ = jax.jit(lambda pp, xx: _moe_block(pp, xx, cfg, rt))(p, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-3, atol=2e-4)
            print("RESIDENT_OK", strat)
        """)
        assert "RESIDENT_OK" in out

    def test_resident_decode_shape_all_strategies(self):
        """True decode tokens (T==1) through each resident layout, with both
        combine collectives (ring psum and H-tree)."""
        out = _run_with_devices(8, """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import moe as MoE
            from repro.models.transformer import _moe_block, Runtime
            from repro.dist import sharding as SH
            cfg = ARCHS["grok-1-314b"].reduced()
            p = MoE.moe_init(jax.random.key(0), cfg)
            x = jax.random.normal(jax.random.key(2), (8, 1, cfg.d_model))
            ref, _ = MoE.moe_apply(p, x, cfg, axis_name=None)
            seen = set()
            for mesh_shape in [(2, 4), (8, 1), (2, 2)]:
                mesh = make_mesh(mesh_shape, ("data", "model"))
                seen.add(SH.moe_serve_strategy(cfg, mesh))
                for coll in ("psum", "htree"):
                    rt = Runtime(mesh=mesh, data_axes=("data",),
                                 serve_resident_moe=True, collective=coll)
                    got, _ = jax.jit(
                        lambda pp, xx: _moe_block(pp, xx, cfg, rt))(p, x)
                    np.testing.assert_allclose(np.asarray(got),
                                               np.asarray(ref),
                                               rtol=2e-3, atol=2e-4)
            assert seen == {"ep_data", "etp2", "ep2"}, seen
            print("RESIDENT_T1_OK", sorted(seen))
        """)
        assert "RESIDENT_T1_OK" in out


class TestShardedServe:
    """The mesh-sharded continuous-batching engine must reproduce the
    single-device engine token-for-token on a ragged multi-request batch
    (scheduling is host-side and identical; only tensor placement moves)."""

    def test_sharded_engine_token_identical(self):
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            # dense quantized (W8A8 decode) + MoE float (resident experts)
            for arch, quantize in (("llama3-8b", True),
                                   ("grok-1-314b", False)):
                cfg = ARCHS[arch].reduced()
                params = M.init_params(jax.random.key(0), cfg)
                rng = np.random.default_rng(7)
                prompts = [rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 17)).tolist()
                           for _ in range(10)]
                budgets = [int(rng.integers(2, 12)) for _ in range(10)]
                ref = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=48,
                    quantize=quantize).generate_all(prompts, budgets)
                mesh = make_mesh((2, 4), ("data", "model"))
                rt = Runtime(mesh=mesh, data_axes=("data",),
                             serve_resident_moe=True)
                got = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=48, quantize=quantize,
                    rt=rt).generate_all(prompts, budgets)
                assert got == ref, (arch, got, ref)
                print("PARITY_OK", arch)
        """)
        assert out.count("PARITY_OK") == 2

    def test_sharded_swap_preempt_token_identical(self):
        """Swap-based preemption over the 2x4 mesh must match the
        single-device recompute engine token-for-token: the lifted slot row
        (read_slot) and the restore write round-trip through replicated
        host blocks (dist.sharding.swap_row_shardings), so tier placement
        never perturbs the sampled/greedy streams.  Fair-share with a tiny
        quantum forces the preemptions; the run must actually swap."""
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            cfg = ARCHS["llama3-8b"].reduced()
            params = M.init_params(jax.random.key(0), cfg)
            rng = np.random.default_rng(29)
            prompts = [rng.integers(0, cfg.vocab_size,
                                    rng.integers(6, 17)).tolist()
                       for _ in range(6)]
            budgets = [int(rng.integers(4, 10)) for _ in range(6)]
            ref = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=32, chunk=4,
                policy="fair:3").generate_all(prompts, budgets)
            mesh = make_mesh((2, 4), ("data", "model"))
            rt = Runtime(mesh=mesh, data_axes=("data",),
                         serve_resident_moe=True)
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=32, chunk=4,
                policy="fair:3", kv_swap=True, rt=rt)
            got = eng.generate_all(prompts, budgets)
            assert got == ref, (got, ref)
            assert eng.stats["preempt_swaps"] > 0
            assert eng.stats["swap_in_bytes"] == eng.stats["swap_out_bytes"]
            print("SWAP_PARITY_OK", "swaps=%d" % eng.stats["preempt_swaps"])
        """)
        assert out.count("SWAP_PARITY_OK") == 1

    def test_sharded_spec_decode_token_identical(self):
        """Speculative decode over the mesh must match the single-device
        *non-speculative* engine token-for-token: the verify step's I/O is
        pinned beside the pool (dist.sharding.verify_shardings) and the
        cursor rollback is a replicated pos rewrite.  Covers the ngram
        drafter on a dense GQA arch (with fair-share preemption riding the
        spec lane) and the MTP drafter on DeepSeek (MLA + MoE + cfg.mtp)."""
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            for arch, quantize, drafter, policy in (
                    ("llama3-8b", True, "ngram", "fair:3"),
                    ("deepseek-v3-671b", False, "mtp", "sjf")):
                cfg = ARCHS[arch].reduced()
                params = M.init_params(jax.random.key(0), cfg)
                rng = np.random.default_rng(13)
                prompts = [rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 13)).tolist()
                           for _ in range(6)]
                budgets = [int(rng.integers(2, 9)) for _ in range(6)]
                ref = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32,
                    quantize=quantize).generate_all(prompts, budgets)
                mesh = make_mesh((2, 4), ("data", "model"))
                rt = Runtime(mesh=mesh, data_axes=("data",),
                             serve_resident_moe=True)
                eng = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32, quantize=quantize,
                    chunk=4, policy=policy, spec_k=4, drafter=drafter, rt=rt)
                got = eng.generate_all(prompts, budgets)
                assert got == ref, (arch, got, ref)
                assert eng.stats["verify_steps"] > 0
                print("SPEC_PARITY_OK", arch,
                      "accept=%.2f" % eng.acceptance_rate)
        """)
        assert out.count("SPEC_PARITY_OK") == 2

    def test_sharded_tree_spec_decode_token_identical(self):
        """The tree-draft spec lane over the 2x4 mesh must match the
        single-device non-speculative engine token-for-token: the [B, T]
        depth/anc window operands pin beside the draft tokens
        (dist.sharding.tree_verify_shardings) and the accepted-path
        compaction (tree_commit) takes the pool in and out at its own
        shardings with replicated scalar operands — the donation-alias
        condition.  Covers the branching ngram drafter on dense GQA (with
        fair-share preemption riding the lane) and the beamed MTP drafter
        on DeepSeek (MLA + MoE + cfg.mtp)."""
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            for arch, quantize, drafter, policy in (
                    ("llama3-8b", True, "ngram", "fair:3"),
                    ("deepseek-v3-671b", False, "mtp", "sjf")):
                cfg = ARCHS[arch].reduced()
                params = M.init_params(jax.random.key(0), cfg)
                rng = np.random.default_rng(13)
                prompts = [rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 13)).tolist()
                           for _ in range(6)]
                budgets = [int(rng.integers(2, 9)) for _ in range(6)]
                ref = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32,
                    quantize=quantize).generate_all(prompts, budgets)
                mesh = make_mesh((2, 4), ("data", "model"))
                rt = Runtime(mesh=mesh, data_axes=("data",),
                             serve_resident_moe=True)
                eng = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32, quantize=quantize,
                    chunk=4, policy=policy, spec_tree=4, spec_branch=2,
                    drafter=drafter, rt=rt)
                got = eng.generate_all(prompts, budgets)
                assert got == ref, (arch, got, ref)
                assert eng.stats["verify_steps"] > 0
                print("TREE_PARITY_OK", arch,
                      "hist=%s" % eng.stats["spec_accept_hist"])
        """)
        assert out.count("TREE_PARITY_OK") == 2

    def test_sharded_multi_step_token_identical(self):
        """The fused multi-step lane over the mesh must match the
        single-device *single-step* engine token-for-token: the fused
        block's in/out shardings pin beside the pool
        (dist.sharding.serve_step_shardings) so the donated SLC pool
        aliases in place, the [B, m] token block is the only decode fetch,
        and the overshoot rollback is a replicated pos rewrite.  Covered
        with chunked prefill riding along (fusion must wait out PREFILLING
        slots) and a trace whose budgets stop mid-block."""
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            cfg = ARCHS["llama3-8b"].reduced()
            params = M.init_params(jax.random.key(0), cfg)
            rng = np.random.default_rng(11)
            prompts = [rng.integers(0, cfg.vocab_size,
                                    rng.integers(3, 15)).tolist()
                       for _ in range(6)]
            budgets = [int(rng.integers(2, 8)) for _ in range(6)]
            ref = ContinuousBatchingEngine(
                cfg, params, n_slots=4,
                max_len=32).generate_all(prompts, budgets)
            mesh = make_mesh((2, 4), ("data", "model"))
            rt = Runtime(mesh=mesh, data_axes=("data",),
                         serve_resident_moe=True)
            for chunk in (None, 4):
                eng = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32, chunk=chunk,
                    multi_step=4, rt=rt)
                got = eng.generate_all(prompts, budgets)
                assert got == ref, (chunk, got, ref)
                assert eng.stats["multi_blocks"] > 0, chunk
                print("MULTI_PARITY_OK", chunk,
                      "blocks=%d" % eng.stats["multi_blocks"])
        """)
        assert out.count("MULTI_PARITY_OK") == 2

    def test_sharded_chunked_prefill_token_identical(self):
        """Chunked prefill over the mesh must match the single-device
        *unchunked* engine: the carry stays pinned
        (prefill_carry_shardings) and RoPE runs partition-safe
        (apply_rope_spmd — rotate-half's split+concat mis-partitions
        deferred partial sums).  GQA and MLA (latent halves carried
        separately) both covered."""
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            for arch, quantize in (("llama3-8b", True),
                                   ("deepseek-v3-671b", False)):
                cfg = ARCHS[arch].reduced()
                params = M.init_params(jax.random.key(0), cfg)
                rng = np.random.default_rng(11)
                prompts = [rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 15)).tolist()
                           for _ in range(6)]
                budgets = [int(rng.integers(2, 8)) for _ in range(6)]
                ref = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32,
                    quantize=quantize).generate_all(prompts, budgets)
                mesh = make_mesh((2, 4), ("data", "model"))
                rt = Runtime(mesh=mesh, data_axes=("data",),
                             serve_resident_moe=True)
                eng = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32, quantize=quantize,
                    chunk=4, policy="sjf", rt=rt)
                got = eng.generate_all(prompts, budgets)
                assert got == ref, (arch, got, ref)
                assert eng.stats["chunks"] > len(prompts)
                print("CHUNK_PARITY_OK", arch)
        """)
        assert out.count("CHUNK_PARITY_OK") == 2

    def test_sharded_prefix_cache_token_identical(self):
        """Warm admissions over the mesh must match the single-device cold
        engine: the row gather and warm-carry seed run with in/out pinned
        beside the pool (dist.sharding.prefix_gather_shardings), so the
        donated pool aliases in place and the copied prefix rows stay
        byte-identical across devices.  Shared-prefix prompts with a pinned
        seed set (warm tails recompute against a dequantized-int8 prefix,
        ~1e-3 logit delta — near-tie argmax flips are possible on random
        smoke weights, so seeds are verified; see DESIGN.md Sec. 1g)."""
        out = _run_with_devices(8, """
            import jax
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            cfg = ARCHS["llama3-8b"].reduced()
            params = M.init_params(jax.random.key(0), cfg)
            shared = jax.random.randint(jax.random.key(2), (10,), 0,
                                        cfg.vocab_size).tolist()
            prompts = [shared + jax.random.randint(
                           jax.random.key(10 + i), (4,), 0,
                           cfg.vocab_size).tolist() for i in range(4)]
            ref = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=48,
                chunk=4).generate_all(prompts, [6] * 4)
            mesh = make_mesh((2, 4), ("data", "model"))
            rt = Runtime(mesh=mesh, data_axes=("data",),
                         serve_resident_moe=True)
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=48, chunk=4,
                prefix_cache=True, rt=rt)
            got = eng.generate_all(prompts, [6] * 4)
            assert got == ref, (got, ref)
            assert eng.stats["prefix_hits"] > 0
            print("PREFIX_PARITY_OK",
                  "hits=%d saved=%d" % (eng.stats["prefix_hits"],
                                        eng.stats["prefill_tokens_saved"]))
        """)
        assert out.count("PREFIX_PARITY_OK") == 1

    def test_sharded_fault_recovery_token_identical(self):
        """Step-level recovery on the 2x4 mesh must match the fault-free
        single-device run.  A failed donated step consumes the sharded pool,
        so ``_rebuild_pool`` re-allocates it with ``jax.device_put`` against
        the recorded state sharding — if the rebuilt pool lands with the
        wrong layout, the retried step either crashes or silently computes
        on garbage rows and token parity breaks.  Slot loss additionally
        exercises resident recovery (recompute-replay) over the mesh."""
        out = _run_with_devices(8, """
            import jax
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            from repro.serve.faults import FaultInjector
            cfg = ARCHS["llama3-8b"].reduced()
            params = M.init_params(jax.random.key(0), cfg)
            prompts = [jax.random.randint(jax.random.key(10 + i), (6,), 0,
                                          cfg.vocab_size).tolist()
                       for i in range(4)]
            ref = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=48,
                chunk=4).generate_all(prompts, [8] * 4)
            mesh = make_mesh((2, 4), ("data", "model"))
            rt = Runtime(mesh=mesh, data_axes=("data",),
                         serve_resident_moe=True)
            eng = ContinuousBatchingEngine(
                cfg, params, n_slots=2, max_len=48, chunk=4, rt=rt,
                faults=FaultInjector(seed=0, step_fail_at=(7, 19),
                                     slot_loss_at=((13, 0),)),
                retry_backoff_s=0.0)
            got = eng.generate_all(prompts, [8] * 4)
            assert got == ref, (got, ref)
            s = eng.stats
            assert s["step_failures"] == 2 and s["pool_rebuilds"] == 2, s
            assert s["slot_losses"] == 1 and s["recovery_recomputes"] >= 1, s
            assert eng.scheduler.quarantined == {0}
            print("FAULT_PARITY_OK",
                  "rebuilds=%d recomputes=%d" % (s["pool_rebuilds"],
                                                 s["recovery_recomputes"]))
        """)
        assert out.count("FAULT_PARITY_OK") == 1


class TestMeshRope:
    """The B=1 atomic prefill routes RoPE through ``apply_rope_spmd`` under
    a mesh (same dispatch the chunked path has always used).  Rotate-half's
    split+concat made XLA's SPMD partitioner fall back to involuntary full
    rematerialization inside the layer scan — visible in the compiled HLO
    as ``copy`` instructions whose metadata points at the ``concatenate``
    in ``layers.apply_rope``."""

    def test_atomic_prefill_mesh_no_rope_remat_copies(self):
        out = _run_with_devices(8, """
            import jax, jax.numpy as jnp
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.launch.hlo_cost import analyse_text
            from repro.dist import sharding as SH
            for arch in ("llama3-8b", "deepseek-v3-671b"):
                cfg = ARCHS[arch].reduced()
                params = M.init_params(jax.random.key(0), cfg)
                mesh = make_mesh((2, 4), ("data", "model"))
                rt = Runtime(mesh=mesh, data_axes=("data",),
                             serve_resident_moe=True)
                params_m = jax.device_put(params, SH.param_shardings(
                    cfg, jax.eval_shape(lambda: params), mesh))
                batch = {"inputs": jnp.zeros((1, 16), jnp.int32),
                         "lengths": jnp.array([12], jnp.int32)}
                hlo = jax.jit(
                    lambda pp, bb: M.prefill(pp, cfg, bb, 32, rt)
                ).lower(params_m, batch).compile().as_text()
                # a rotate-half remat copy carries the concatenate op_name
                # with layers.py provenance; post-fix there are none
                bad = [l for l in hlo.splitlines()
                       if " copy(" in l and "concatenate" in l
                       and "layers.py" in l]
                assert not bad, (arch, bad[:2])
                cost = analyse_text(hlo)
                assert cost["collectives"].get("total", 0) > 0, arch
                print("NO_ROPE_REMAT", arch,
                      "bytes=%.3e" % cost["bytes_accessed"])
        """)
        assert out.count("NO_ROPE_REMAT") == 2

    def test_seed17_rope_parity_pinned(self):
        """Pins the seed-17 near-tie outcome after the atomic RoPE fix.

        Before the fix the meshed *atomic* MLA prefill produced logits far
        enough from the single-device reference that even first tokens
        flipped (rotate-half's remat path).  After it: GQA is
        token-identical atomic+chunked, MLA is token-identical chunked,
        and MLA atomic now agrees on every first token — the residual
        later-step divergence is mesh float-accumulation order flipping
        genuine argmax near-ties in the MLA decode path (decode still uses
        rotate-half; reduction order differs across partitions), which no
        RoPE routing can remove."""
        out = _run_with_devices(8, """
            import jax, numpy as np
            from repro.configs.registry import ARCHS
            from repro.models import model as M
            from repro.models.transformer import Runtime
            from repro.serve.engine import ContinuousBatchingEngine
            for arch, quantize in (("llama3-8b", True),
                                   ("deepseek-v3-671b", False)):
                cfg = ARCHS[arch].reduced()
                params = M.init_params(jax.random.key(0), cfg)
                rng = np.random.default_rng(17)
                prompts = [rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 15)).tolist()
                           for _ in range(6)]
                budgets = [int(rng.integers(2, 8)) for _ in range(6)]
                ref = ContinuousBatchingEngine(
                    cfg, params, n_slots=4, max_len=32,
                    quantize=quantize).generate_all(prompts, budgets)
                mesh = make_mesh((2, 4), ("data", "model"))
                rt = Runtime(mesh=mesh, data_axes=("data",),
                             serve_resident_moe=True)
                for chunk in (None, 4):
                    eng = ContinuousBatchingEngine(
                        cfg, params, n_slots=4, max_len=32,
                        quantize=quantize, chunk=chunk, policy="sjf",
                        rt=rt)
                    got = eng.generate_all(prompts, budgets)
                    if arch == "deepseek-v3-671b" and chunk is None:
                        # MLA atomic: first tokens must match (the fix);
                        # later steps may near-tie diverge (documented)
                        assert [g[0] for g in got] == [r[0] for r in ref]
                        print("SEED17_FIRST_TOKEN_OK", arch)
                    else:
                        assert got == ref, (arch, chunk, got, ref)
                        print("SEED17_PARITY_OK", arch,
                              "chunk" if chunk else "atomic")
        """)
        assert out.count("SEED17_PARITY_OK") == 3
        assert out.count("SEED17_FIRST_TOKEN_OK") == 1
