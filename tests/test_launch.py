"""The train driver's entry point and the launchers' compile cache."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import train
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is the cache: JAX read it at start
    and the helper sets no other directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert enable_compile_cache() == str(DEFAULT_DIR)   # stable path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert "/.jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_train_main_takes_argv_and_returns_its_record(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = train.main(["--steps", "2", "--workers", "4", "--byz", "1",
                      "--seq", "16", "--batch", "2", "--log-every", "1"])
    hist = out["history"]
    assert [r["step"] for r in hist] == [1, 2]
    for r in hist:
        assert np.isfinite(r["loss"]) and np.isfinite(r["kappa_hat"])
        assert r["seconds"] > 0
    assert out["compile_seconds"] > 0
    assert out["n_params"] == sum(
        x.size for x in jax.tree_util.tree_leaves(out["state"]["params"]))
    assert int(out["state"]["step"]) == 2
    assert out["memory"].argument_size_in_bytes > 0


def test_train_full_refuses_to_start_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU --full is allowed")
    with pytest.raises(SystemExit, match="TPU is required"):
        train.main(["--full", "--steps", "1"])
