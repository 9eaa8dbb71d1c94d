"""End-to-end behaviour tests for the paper's system: the full offload
pipeline (prefill -> KV handoff -> quantized decode) on a small model."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.configs.shapes import ShapeConfig
from repro.data.pipeline import SyntheticTokens
from repro.models import model as M
from repro.models.transformer import Runtime
from repro.serve.engine import Engine

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def opt125_engine():
    cfg = ARCHS["opt-125m"].reduced()
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, Engine(cfg=cfg, params=params, max_len=64)


class TestServeEngine:
    def test_generate_batched(self, opt125_engine):
        cfg, eng = opt125_engine
        prompts = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
        toks, times = eng.generate({"inputs": prompts}, steps=8)
        assert toks.shape == (4, 8)
        assert (toks >= 0).all() and (toks < cfg.vocab_size).all()
        assert times["tpot_s"] > 0

    def test_greedy_deterministic(self, opt125_engine):
        cfg, eng = opt125_engine
        prompts = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab_size)
        t1, _ = eng.generate({"inputs": prompts}, steps=6)
        t2, _ = eng.generate({"inputs": prompts}, steps=6)
        assert (t1 == t2).all()

    def test_quantized_matches_float_generation(self):
        """The W8A8 'PIM' decode produces (near-)identical greedy tokens."""
        cfg = ARCHS["opt-125m"].reduced()
        params = M.init_params(jax.random.key(3), cfg)
        prompts = jax.random.randint(jax.random.key(4), (2, 16), 0, cfg.vocab_size)
        eq = Engine(cfg=cfg, params=params, max_len=64, quantize=True)
        ef = Engine(cfg=cfg, params=params, max_len=64, quantize=False)
        tq, _ = eq.generate({"inputs": prompts}, steps=8)
        tf, _ = ef.generate({"inputs": prompts}, steps=8)
        agree = float((tq == tf).mean())
        assert agree >= 0.75, f"only {agree:.0%} token agreement"


class TestTrainingEndToEnd:
    def test_short_training_run_improves(self):
        from repro.optim.adamw import AdamW
        from repro.train.train_step import make_train_step
        cfg = ARCHS["opt-125m"].reduced()
        shape = ShapeConfig("tiny", 32, 4, "train")
        data = SyntheticTokens(cfg, shape, seed=0)
        params = M.init_params(jax.random.key(0), cfg)
        opt = AdamW(lr=2e-3, warmup_steps=2, total_steps=50, weight_decay=0.0)
        step = jax.jit(make_train_step(cfg, Runtime(), opt))
        st = opt.init(params)
        first = last = None
        for i in range(15):
            params, st, m = step(params, st, data.batch_at(i % 3))
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
        assert last < first - 0.5


class TestEncDecServing:
    def test_whisper_engine_generates(self):
        """End-to-end enc-dec serving: stub audio frames -> prefill (encoder
        + int8 cross-KV) -> cached decode."""
        from repro.configs.registry import ARCHS
        cfg = ARCHS["whisper-tiny"].reduced()
        params = M.init_params(jax.random.key(0), cfg)
        eng = Engine(cfg=cfg, params=params, max_len=48)
        batch = {"frames": jax.random.normal(jax.random.key(1),
                                             (2, cfg.encoder_seq, cfg.d_model)),
                 "tokens": jax.random.randint(jax.random.key(2), (2, 8), 0,
                                              cfg.vocab_size)}
        toks, times = eng.generate(batch, steps=6)
        assert toks.shape == (2, 6)
        assert bool((toks >= 0).all()) and bool((toks < cfg.vocab_size).all())


class TestServeCli:
    """``repro.launch.serve`` must not report a run in which a request
    failed as a success: the engine isolates the failure and keeps serving
    the other requests, so only the exit code tells."""

    @pytest.mark.parametrize("mode", ["--continuous", "--serve"])
    def test_failed_admission_exits_nonzero(self, monkeypatch, capsys, mode):
        from repro.launch import serve
        real, calls = M.prefill, []

        def flaky_prefill(*args, **kwargs):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("injected admission failure")
            return real(*args, **kwargs)

        monkeypatch.setattr(M, "prefill", flaky_prefill)
        monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
        monkeypatch.setattr(sys, "argv", [
            "serve", "--arch", "llama3-8b", "--reduced", mode,
            "--requests", "3", "--slots", "2", "--prompt-len", "8",
            "--steps", "4"])
        with pytest.raises(SystemExit) as exc:
            serve.main()
        assert exc.value.code not in (None, 0)
        assert "injected admission failure" in capsys.readouterr().out


class TestCompileCache:
    def test_placement(self, monkeypatch, tmp_path):
        """``JAX_COMPILATION_CACHE_DIR`` wins untouched; without it the
        cache sits at the checkout's fixed ``.jax_cache``."""
        from repro.launch import compile_cache as CC
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        try:
            path = CC.enable_compile_cache()
            assert path == str(Path(__file__).resolve().parents[1]
                               / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
