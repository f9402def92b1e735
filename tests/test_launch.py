"""Entry-point plumbing: the compile-cache rule and the chip smoke's
refusal to run anywhere but a TPU."""
import contextlib
import importlib.util
import io
import os

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_environment(monkeypatch, restore_cache_config):
    """A set JAX_COMPILATION_CACHE_DIR is used and nothing else is set."""
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_checkout(monkeypatch, restore_cache_config):
    """Without it, the cache is the fixed <checkout>/.jax_cache path."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_chip_smoke_refuses_cpu():
    """On the CPU the smoke exits non-zero and prints no result line."""
    assert jax.devices()[0].platform != "tpu"
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = smoke.main([])
    assert rc != 0
    assert out.getvalue() == ""
    assert "no TPU" in err.getvalue()
