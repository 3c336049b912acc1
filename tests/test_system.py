"""End-to-end system behaviour: the launchers drive the full stack."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture
def compile_cache_dir(monkeypatch, tmp_path):
    """Point the entry points' persistent compilation cache at a fresh
    directory; restore JAX's cache state afterwards so later tests in
    this process compile as before."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    path = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    cc.reset_cache()
    yield path
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    cc.reset_cache()


def test_compile_cache_lands_where_configured(compile_cache_dir,
                                              monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and executables land there; unset,
    the cache is one fixed directory at the root of the checkout."""
    from repro import compile_cache
    assert compile_cache.enable() == str(compile_cache_dir)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert any(compile_cache_dir.iterdir())
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(path) == root


def test_train_launcher_end_to_end(tmp_path):
    """train.py: init -> sharded train -> checkpoint -> resume."""
    from repro.launch.train import main
    hist = main(["--arch", "smollm-360m", "--smoke", "--steps", "30",
                 "--seq", "32", "--batch", "4", "--lr", "5e-3",
                 "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "10"])
    assert hist["loss"][-1] < hist["loss"][0]
    # resume picks up from the written checkpoint
    hist2 = main(["--arch", "smollm-360m", "--smoke", "--steps", "10",
                  "--seq", "32", "--batch", "4", "--lr", "5e-3",
                  "--ckpt-dir", str(tmp_path / "ck"), "--resume"])
    assert np.isfinite(hist2["loss"][-1])


def test_train_launcher_with_compression():
    from repro.launch.train import main
    hist = main(["--arch", "smollm-360m", "--smoke", "--steps", "20",
                 "--seq", "32", "--batch", "4", "--lr", "5e-3",
                 "--compress-grads"])
    assert hist["loss"][-1] < hist["loss"][0]


@pytest.mark.slow
def test_serve_launcher_end_to_end(tmp_path, compile_cache_dir):
    """serve.py: deploy -> trace replay -> cold/warm statistics; its
    executables go to the configured compilation cache."""
    from repro.launch.serve import main
    responses = main(["--models", "smollm-360m", "--strategy", "cicada",
                      "--invocations", "6", "--duration", "60",
                      "--keep-alive", "1000",
                      "--store", str(tmp_path / "store"),
                      "--bandwidth-mbps", "500"])
    assert len(responses) == 6
    colds = [r for r in responses if r.cold]
    warms = [r for r in responses if not r.cold]
    assert len(colds) >= 1 and len(warms) >= 1
    # warm requests are much faster than cold starts
    assert (np.mean([r.latency_s for r in warms])
            < np.mean([r.latency_s for r in colds]))
    assert any(compile_cache_dir.iterdir())
