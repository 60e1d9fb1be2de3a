"""Process start-up rules (utils/xla_env.py): the compile cache is
placed from outside or at one normalised path, and a device-owning
process never serves off a CPU backend it did not ask for."""

import os

import jax
import pytest

from fabric_tpu.utils import xla_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_dir_from_environment_is_left_alone(
        monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jax.config.update("jax_compilation_cache_dir", "/somewhere/else")
    assert xla_env.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == "/somewhere/else"


def test_compile_cache_dir_defaults_to_normalised_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    want = os.path.join(REPO, ".jax_cache")
    assert xla_env.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.normpath(want) == want


def test_claim_device_refuses_an_implicit_cpu_backend(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        xla_env.claim_device("test")


def test_claim_device_accepts_an_explicit_cpu_backend(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    dev = xla_env.claim_device("test")
    assert dev["platform"] == "cpu"
    assert dev["count"] == len(jax.devices())
    assert dev["kind"] == jax.devices()[0].device_kind


def test_cpu_compile_flag_only_where_cpu_is_named(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    xla_env.ensure_cpu_compile_workaround()
    assert os.environ["XLA_FLAGS"] == ""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    xla_env.ensure_cpu_compile_workaround()
    assert "xla_cpu_use_fusion_emitters=false" in os.environ["XLA_FLAGS"]
