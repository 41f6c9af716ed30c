"""Launch layer: compile-cache placement and the ``run()`` entry points."""
from pathlib import Path

import jax
import numpy as np

from repro.launch import compile_cache, serve, train


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_run_answers_every_request():
    out = serve.run("internlm2_1_8b", smoke=True, max_seq=32, requests=20, batch_rows=8)
    assert len(out["requests"]) == len(out["next_token"]) == len(out["logprob"]) == 20
    assert np.all(np.isfinite(out["logprob"])) and np.all(out["logprob"] <= 0)
    assert np.all((out["next_token"] >= 0) & (out["next_token"] < out["model"].cfg.vocab))


def test_train_run_over_flight_without_restarts(tmp_path):
    out = train.run("xlstm_350m", ckpt_dir=str(tmp_path), smoke=True, steps=3,
                    batch_size=2, seq_len=64, checkpoint_every=2, log=lambda *_: None)
    assert out["restarts"] == 0 and out["step"] == 3
    assert len(out["losses"]) == 3 and np.all(np.isfinite(out["losses"]))
    assert (tmp_path / "step_000000002" / "manifest.json").exists()
