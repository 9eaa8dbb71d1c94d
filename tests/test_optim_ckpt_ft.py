"""Optimizer, checkpointing, and fault-tolerance tests."""
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as C
from repro.configs.registry import ARCHS
from repro.data.pipeline import SyntheticTokens
from repro.configs.shapes import ShapeConfig
from repro.ft import compress as FC
from repro.ft.failures import FailureInjector, ResilientRunner, StragglerWatchdog
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.transformer import Runtime
from repro.optim.adamw import AdamW
from repro.train.train_step import make_train_step

jax.config.update("jax_platform_name", "cpu")


class TestAdamW:
    def _rosenbrockish(self, opt, steps=200):
        params = {"x": jnp.array([2.0, -1.5]), "w": jnp.ones((4, 4))}
        target = jnp.array([0.5, 0.5])
        state = opt.init(params)

        def loss(p):
            return jnp.sum((p["x"] - target) ** 2) + 0.1 * jnp.sum(p["w"] ** 2)

        for _ in range(steps):
            g = jax.grad(loss)(params)
            params, state, _ = opt.update(g, state, params)
        return float(loss(params))

    def test_converges(self):
        assert self._rosenbrockish(AdamW(lr=5e-2, weight_decay=0.0,
                                         warmup_steps=5, total_steps=10_000)) < 1e-2

    def test_int8_moments_track_fp32(self):
        l32 = self._rosenbrockish(AdamW(lr=5e-2, weight_decay=0.0, warmup_steps=5))
        l8 = self._rosenbrockish(AdamW(lr=5e-2, weight_decay=0.0, warmup_steps=5,
                                       quantized_state=True))
        assert abs(l8 - l32) < 0.05

    def test_grad_clip(self):
        opt = AdamW(clip_norm=1.0)
        params = {"x": jnp.zeros(3)}
        state = opt.init(params)
        _, _, gnorm = opt.update({"x": jnp.full(3, 1e6)}, state, params)
        assert float(gnorm) > 1e5  # reported pre-clip norm


class TestTrainStepLossDecreases:
    def test_tiny_llama_loss_goes_down(self):
        cfg = ARCHS["llama3-8b"].reduced()
        shape = ShapeConfig("tiny", 32, 4, "train")
        data = SyntheticTokens(cfg, shape, seed=3)
        params = M.init_params(jax.random.key(0), cfg)
        opt = AdamW(lr=1e-3, warmup_steps=2, total_steps=100, weight_decay=0.0)
        ostate = opt.init(params)
        step = jax.jit(make_train_step(cfg, Runtime(), opt))
        losses = []
        for i in range(12):
            params, ostate, m = step(params, ostate, data.batch_at(i % 2))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.3, losses

    def test_microbatched_matches_full(self):
        cfg = ARCHS["granite-3-8b"].reduced()
        shape = ShapeConfig("tiny", 16, 4, "train")
        data = SyntheticTokens(cfg, shape, seed=1)
        params = M.init_params(jax.random.key(0), cfg)
        opt = AdamW(lr=1e-3, warmup_steps=1, total_steps=100)
        s1 = jax.jit(make_train_step(cfg, Runtime(), opt, microbatches=1))
        s2 = jax.jit(make_train_step(cfg, Runtime(), opt, microbatches=2))
        b = data.batch_at(0)
        p1, _, m1 = s1(params, opt.init(params), b)
        p2, _, m2 = s2(params, opt.init(params), b)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3
        d = max(float(jnp.abs(a - b_).max())
                for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 2e-3


class TestCheckpoint:
    def test_roundtrip_bit_exact(self, tmp_path):
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.array(7, jnp.int32),
                      "d": [jnp.ones(5), jnp.zeros(2)]}}
        C.save(tmp_path, 5, tree, {"data_step": 5})
        got, extra = C.restore(tmp_path, tree)
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert extra["data_step"] == 5

    def test_uncommitted_invisible(self, tmp_path):
        tree = {"a": jnp.ones(3)}
        p = C.save(tmp_path, 1, tree)
        (p / "COMMIT").unlink()
        assert C.latest_step(tmp_path) is None

    def test_async_and_gc(self, tmp_path):
        ck = C.AsyncCheckpointer(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, {"a": jnp.full(3, float(s))})
        ck.wait()
        assert C.latest_step(tmp_path) == 4
        steps = sorted(int(p.name.split("_")[1]) for p in Path(tmp_path).glob("step_*"))
        assert steps == [3, 4]

    def test_elastic_reshard_roundtrip(self, tmp_path):
        """Save unsharded, restore onto a (1, n)-device mesh sharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        C.save(tmp_path, 1, tree)
        mesh = make_mesh((1,), ("model",))
        sh = {"w": NamedSharding(mesh, P(None, None))}
        got, _ = C.restore(tmp_path, tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


class TestResilience:
    def _run(self, fail_at, tmp, n_steps=20):
        cfg = ARCHS["granite-3-8b"].reduced()
        shape = ShapeConfig("tiny", 16, 2, "train")
        data = SyntheticTokens(cfg, shape, seed=7)
        params = M.init_params(jax.random.key(0), cfg)
        opt = AdamW(lr=1e-3, warmup_steps=1, total_steps=100)
        step = jax.jit(make_train_step(cfg, Runtime(), opt))
        runner = ResilientRunner(step_fn=step, ckpt_dir=str(tmp), ckpt_every=5,
                                 injector=FailureInjector(fail_at=fail_at))
        p, o, log = runner.run(params, opt.init(params), data, n_steps,
                               async_ckpt=False)
        return p, log

    def test_recovers_and_matches_clean_run(self, tmp_path):
        p_clean, log_clean = self._run((), tmp_path / "clean")
        p_fail, log_fail = self._run((7, 13), tmp_path / "fail")
        for a, b in zip(jax.tree.leaves(p_clean), jax.tree.leaves(p_fail)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        # the failed run replayed steps deterministically
        clean_losses = {m["step"]: m["loss"] for m in log_clean}
        for m in log_fail:
            assert abs(m["loss"] - clean_losses[m["step"]]) < 1e-5

    def test_straggler_watchdog(self):
        wd = StragglerWatchdog(factor=2.0)
        for s, dt in enumerate([1.0, 1.0, 1.0, 5.0, 1.0]):
            wd.observe(s, dt)
        assert len(wd.events) == 1 and wd.events[0][0] == 3


class TestGradCompression:
    def test_error_feedback_converges_exactly_in_expectation(self):
        g = jax.random.normal(jax.random.key(0), (256,))
        res = jnp.zeros(256)
        acc = jnp.zeros(256)
        for _ in range(50):
            q, s, res = FC.compress(g, res)
            acc = acc + FC.decompress(q, s)
        # time-averaged compressed stream == true gradient (EF property)
        np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g),
                                   atol=float(s) * 1.1)

    def test_quantization_bounded(self):
        g = jax.random.normal(jax.random.key(1), (64,)) * 10
        q, s, res = FC.compress(g, jnp.zeros(64))
        assert float(jnp.abs(res).max()) <= float(s) * 0.51


class TestDataPipeline:
    def test_deterministic_skip_ahead(self):
        cfg = ARCHS["llama3-8b"].reduced()
        shape = ShapeConfig("tiny", 8, 4, "train")
        a = SyntheticTokens(cfg, shape, seed=11)
        b = SyntheticTokens(cfg, shape, seed=11).skip_to(3)
        for _ in range(3):
            next(a)
        ba, bb = next(a), next(b)
        np.testing.assert_array_equal(ba["inputs"], bb["inputs"])
        np.testing.assert_array_equal(ba["labels"], bb["labels"])
