"""``chip_smoke.py`` on the CPU, and where the launchers keep the compile
cache.

The smoke's phases run here at the smoke config (the jnp reference
kernels); its ``main`` must refuse to run without a TPU and print no
result.
"""

import importlib.util
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                        setup_compilation_cache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_ARGV = ["--arch", "qwen2.5-1.5b", "--smoke", "--paged",
              "--prefix-sharing", "--page-size", "16", "--lanes", "2",
              "--requests", "4", "--prompt-len", "32",
              "--shared-prefix-len", "16", "--gen", "8"]


@pytest.fixture
def cache_config():
    """Restore the persistent-cache settings the launchers change."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.fixture
def chip_smoke(cache_config):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_phase_smoke_config(chip_smoke):
    summary = chip_smoke.served_phase(SMOKE_ARGV)
    assert summary["tokens"] == 2 * 4 * 8
    assert summary["prefix_hits"] > 0
    assert summary["second_batch_compiles"] == 0
    # the CPU lowers the jnp reference, never the Pallas kernel
    assert summary["decode_tpu_custom_calls"] == 0


def test_kernel_parity_phase_reference_path(chip_smoke):
    assert chip_smoke.kernel_parity_phase() <= chip_smoke.PARITY_ATOL


def test_main_refuses_cpu_backend(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_default_is_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert setup_compilation_cache() == DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR


def test_compile_cache_follows_environment(monkeypatch, cache_config,
                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = set(os.listdir(DEFAULT_CACHE_DIR)) \
        if os.path.isdir(DEFAULT_CACHE_DIR) else set()
    assert setup_compilation_cache() == str(tmp_path)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    assert os.listdir(tmp_path), "nothing was cached in the given dir"
    after = set(os.listdir(DEFAULT_CACHE_DIR)) \
        if os.path.isdir(DEFAULT_CACHE_DIR) else set()
    assert after == before
