"""Compile the served path's kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler compiles for a chip that is only
described (``v5e:2x2``), which refuses what interpret mode accepts
(misaligned tiles, too much VMEM, a program too large for 16 GB).  All
shapes are the full published widths of qwen2.5-1.5b, and the paged
kernel also at the shape olmo-1b is served at.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.  Keep these tests in this one file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import (decode_attention_paged,
                                                decode_attention_paged_q8)
from repro.models import build_model
from repro.models.transformer import (CACHE_SHARED_KEYS, init_paged_cache,
                                      lm_decode_n_steps)

CFG = get_config("qwen2.5-1.5b")
LANES, PAGE, POSITIONS = 8, 16, 4096
TABLE = POSITIONS // PAGE
N_PAGES = LANES * TABLE + 1              # + the engine's scratch page
HBM_BYTES = 16 * 10**9
# olmo-1b as the benchmark serves it: 32 lanes of 2048 positions in
# 64-token pages, 420 pages + the scratch page, MHA 16:16
OLMO = get_config("olmo-1b")
OLMO_LANES, OLMO_PAGE, OLMO_TABLE, OLMO_PAGES = 32, 64, 32, 421
OLMO_POSITIONS = OLMO_TABLE * OLMO_PAGE


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


_SHAPES = {  # lanes, table width, pages, page size, config
    "qwen": (LANES, TABLE, N_PAGES, PAGE, CFG),
    "olmo": (OLMO_LANES, OLMO_TABLE, OLMO_PAGES, OLMO_PAGE, OLMO),
}


@pytest.mark.parametrize("model,pool_dtype", [
    pytest.param("qwen", jnp.bfloat16, id="bf16_pool"),
    pytest.param("qwen", jnp.float32, id="f32_pool"),
    pytest.param("olmo", jnp.bfloat16, id="olmo-bf16_pool"),
    pytest.param("olmo", jnp.float32, id="olmo-f32_pool"),
])
def test_paged_decode_kernel_compiles(one_chip, model, pool_dtype):
    # the stacked pool of every layer, read and written at one of them
    lanes, table, n_pages, page, cfg = _SHAPES[model]
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pool = _sds(one_chip, (cfg.n_layers, n_pages, hkv, page, d), pool_dtype)
    new = _sds(one_chip, (lanes, hkv, d), jnp.bfloat16)
    lane = _sds(one_chip, (lanes,), jnp.int32)
    compiled = decode_attention_paged.lower(
        _sds(one_chip, (lanes, h, d), jnp.bfloat16), new, new, pool, pool,
        _sds(one_chip, (lanes, table), jnp.int32), lane, lane,
        _sds(one_chip, (), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_q8_kernel_compiles(one_chip):
    h, hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.hd
    vals = _sds(one_chip, (CFG.n_layers, N_PAGES, hkv, PAGE, d), jnp.int8)
    scales = _sds(one_chip, (CFG.n_layers, N_PAGES, hkv, PAGE), jnp.float32)
    new = _sds(one_chip, (LANES, hkv, d), jnp.int8)
    new_scale = _sds(one_chip, (LANES, hkv), jnp.float32)
    lane = _sds(one_chip, (LANES,), jnp.int32)
    compiled = decode_attention_paged_q8.lower(
        _sds(one_chip, (LANES, h, d), jnp.bfloat16), new, new_scale, new,
        new_scale, vals, scales, vals, scales,
        _sds(one_chip, (LANES, TABLE), jnp.int32), lane, lane,
        _sds(one_chip, (), jnp.int32)).compile()
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


def _dispatch(cfg, positions, params, cache, state, tokens, rng, remaining,
              lane_seed, tok_idx):
    """The engine's decode dispatch: the per-lane vectors and the
    donated per-layer state as two arguments, 8 steps."""
    return lm_decode_n_steps(params, cfg, {**cache, **state}, tokens, rng,
                             remaining, lane_seed, tok_idx, n_steps=8,
                             len_cap=positions - 1)


#: ops that would move a pool-sized value outside the kernels
_POOL_MOVES = {"copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
               "scatter", "fusion"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = (.+?) ([\w\-]+)\(")


@pytest.mark.parametrize("model", ["olmo", "qwen"])
def test_decode_dispatch_updates_the_pool_in_place(one_chip, model):
    """The dispatch as the engine jits it, at the served shapes (olmo-1b:
    32 lanes of 2048 positions in 64-token pages, 421 pages; qwen2.5-1.5b:
    8 lanes of 4096 in 16-token pages), f32 parameters: no instruction
    outside the Pallas kernels produces one layer's pool or the whole
    pool, and the donated pools are aliased to the outputs."""
    lanes, table, n_pages, page, cfg = _SHAPES[model]
    positions = table * page
    params = _on(one_chip, jax.eval_shape(build_model(cfg).init,
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(functools.partial(
        init_paged_cache, cfg, lanes, positions, page_size=page,
        n_pages=n_pages)))
    state = {k: x for k, x in cache.items() if k not in CACHE_SHARED_KEYS}
    cache = {k: cache[k] for k in CACHE_SHARED_KEYS}
    key = jax.eval_shape(jax.random.PRNGKey, 0)
    lane = _sds(one_chip, (lanes,), jnp.int32)
    step = jax.jit(functools.partial(_dispatch, cfg, positions),
                   donate_argnames=("state",))
    compiled = step.lower(params, cache, state, lane,
                          _sds(one_chip, key.shape, key.dtype), lane, lane,
                          lane).compile()
    pool = state["k_pages"].shape
    shapes = ("[" + ",".join(map(str, pool)) + "]",
              "[" + ",".join(map(str, pool[1:])) + "]")
    moved = []
    for line in compiled.as_text().splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) in _POOL_MOVES and any(
                sh in m.group(1) for sh in shapes):
            moved.append(line.strip()[:160])
    assert not moved, moved
    pool_bytes = state["k_pages"].size * state["k_pages"].dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
