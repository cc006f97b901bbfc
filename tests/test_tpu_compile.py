"""Compile the served path's kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler compiles for a chip that is only
described (``v5e:2x2``), which refuses what interpret mode accepts
(misaligned tiles, too much VMEM, a program too large for 16 GB).  All
shapes are the full published widths of qwen2.5-1.5b.  Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.  Keep these tests in this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import (decode_attention_paged,
                                                decode_attention_paged_q8)
from repro.models import build_model
from repro.models.transformer import init_paged_cache, lm_decode_n_steps

CFG = get_config("qwen2.5-1.5b")
LANES, PAGE, POSITIONS = 8, 16, 4096
TABLE = POSITIONS // PAGE
N_PAGES = LANES * TABLE + 1              # + the engine's scratch page
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: _sds(sharding, x.shape, x.dtype), tree)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_pool", "f32_pool"])
def test_paged_decode_kernel_compiles(one_chip, pool_dtype):
    # the f32 pool is what the int8-KV model path hands the kernel
    h, hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.hd
    pool = _sds(one_chip, (N_PAGES, hkv, PAGE, d), pool_dtype)
    compiled = decode_attention_paged.lower(
        _sds(one_chip, (LANES, h, d), jnp.bfloat16), pool, pool,
        _sds(one_chip, (LANES, TABLE), jnp.int32),
        _sds(one_chip, (LANES,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_q8_kernel_compiles(one_chip):
    h, hkv, d, qblock = CFG.n_heads, CFG.n_kv_heads, CFG.hd, 16
    vals = _sds(one_chip, (N_PAGES, hkv, PAGE, d), jnp.int8)
    scales = _sds(one_chip, (N_PAGES, hkv, PAGE // qblock, 1), jnp.float32)
    compiled = decode_attention_paged_q8.lower(
        _sds(one_chip, (LANES, h, d), jnp.bfloat16), vals, scales, vals,
        scales, _sds(one_chip, (LANES, TABLE), jnp.int32),
        _sds(one_chip, (LANES,), jnp.int32), qblock=qblock).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_n_steps_program_fits_one_chip(one_chip):
    """The engine's whole decode dispatch: full qwen2.5-1.5b, 8 lanes of
    4096 positions, 8 steps per dispatch, f32 parameters."""
    model = build_model(CFG)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(functools.partial(
        init_paged_cache, CFG, LANES, POSITIONS, page_size=PAGE,
        n_pages=N_PAGES)))
    key = jax.eval_shape(jax.random.PRNGKey, 0)
    lane = _sds(one_chip, (LANES,), jnp.int32)
    step = jax.jit(functools.partial(lm_decode_n_steps, cfg=CFG, n_steps=8,
                                     len_cap=POSITIONS - 1))
    compiled = step.lower(params, cache=cache, tokens=lane,
                          rng=_sds(one_chip, key.shape, key.dtype),
                          remaining=lane, lane_seed=lane,
                          tok_idx=lane).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert live < HBM_BYTES, live
