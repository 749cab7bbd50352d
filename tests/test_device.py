"""Device plumbing of the entry points: compile-cache placement, the TPU
requirement, Pallas interpret selection and the peak-rate table."""
import os

import jax
import pytest

from repro.kernels import ops
from repro.launch import device, roofline


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads it


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device.enable_compile_cache()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_compile_cache_keys_on_named_scopes(monkeypatch, restore_cache_dir,
                                            env):
    """Scopes are metadata: without it in the key, a program would load an
    entry compiled without its scopes."""
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    device.enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_require_tpu_names_the_platform():
    with pytest.raises(SystemExit, match="'cpu'"):
        device.require_tpu()


@pytest.mark.parametrize("backend,interpret",
                         [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._default_interpret()
    else:
        assert ops._default_interpret() is interpret


def test_peaks_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no peak rates"):
        roofline.peaks("cpu")
