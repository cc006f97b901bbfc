"""Decoder-only LM assembly covering dense / moe / ssm / hybrid / vlm.

One block definition per family, layers stacked along a leading axis and
executed with ``jax.lax.scan`` (HLO is O(1) in depth -> 80-layer dry-runs
compile in seconds).  Training, prefill (cache build) and single-token
decode all share the same per-layer functions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import (attention_decode, attention_decode_paged,
                                    attention_forward, init_attention)
from repro.models.common import (ModelConfig, apply_norm, cross_entropy, layer_scan,
                                 embed, init_embedding, init_norm, lm_logits,
                                 split_keys)
from repro.models.mlp import init_swiglu, swiglu
from repro.models.moe import init_moe, moe_forward
from repro.models.ssm import (init_mamba2, init_mamba2_state, mamba2_decode,
                              mamba2_forward)
from repro.parallel.sharding import constrain

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# Blocks
# ----------------------------------------------------------------------

def init_block(key, cfg: ModelConfig) -> Params:
    keys = split_keys(key, 6)
    p: Params = {"norm1": init_norm(cfg)}
    if cfg.family == "ssm":
        p["ssm"] = init_mamba2(keys[0], cfg)
        return p
    p["attn"] = init_attention(keys[0], cfg)
    p["norm2"] = init_norm(cfg)
    if cfg.family == "moe":
        p["moe"] = init_moe(keys[1], cfg.d_model, cfg.moe)
        if cfg.moe.dense_residual:
            p["mlp"] = init_swiglu(keys[2], cfg.d_model, cfg.d_ff)
    elif cfg.family == "hybrid":
        p["ssm"] = init_mamba2(keys[1], cfg)
        p["mlp"] = init_swiglu(keys[2], cfg.d_model, cfg.d_ff)
    else:  # dense / vlm backbone
        p["mlp"] = init_swiglu(keys[1], cfg.d_model, cfg.d_ff)
    return p


def _sp_in(h):
    """Megatron-SP boundary: gather the sequence dim before projections
    so the TP (`model`) axis is free for weight shards -- otherwise GSPMD
    resolves the seq-vs-d_ff axis conflict by fully gathering the weight
    matrices (GBs/layer)."""
    return constrain(h, "batch", None, None)


def _sp_out(y):
    """Reduce-scatter block output back to the sequence-sharded stream."""
    return constrain(y, "batch", "seq", None)


def block_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                  positions=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence block. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = _sp_in(apply_norm(p["norm1"], x, cfg.norm))
    if cfg.family == "ssm":
        return x + _sp_out(mamba2_forward(p["ssm"], h, cfg)), aux
    if cfg.family == "hybrid":
        # Hymba: parallel attention + SSM heads over the same input,
        # fused by averaging (arXiv:2411.13676, simplified combiner).
        att = attention_forward(p["attn"], h, cfg, positions=positions)
        ssm = mamba2_forward(p["ssm"], h, cfg)
        x = x + _sp_out(0.5 * (att + ssm))
        h2 = _sp_in(apply_norm(p["norm2"], x, cfg.norm))
        return x + _sp_out(swiglu(p["mlp"], h2)), aux
    x = x + _sp_out(attention_forward(p["attn"], h, cfg,
                                      positions=positions))
    h2 = _sp_in(apply_norm(p["norm2"], x, cfg.norm))
    if cfg.family == "moe":
        mout, aux = moe_forward(p["moe"], h2, cfg.moe)
        if cfg.moe.dense_residual:
            mout = mout + swiglu(p["mlp"], h2)
        return x + _sp_out(mout), aux
    return x + _sp_out(swiglu(p["mlp"], h2)), aux


# ----------------------------------------------------------------------
# Model init / forward
# ----------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> Params:
    kemb, kblocks, kfinal = split_keys(key, 3)
    blocks = [init_block(jax.random.fold_in(kblocks, i), cfg)
              for i in range(cfg.n_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": init_embedding(kemb, cfg),
        "blocks": stacked,
        "final_norm": init_norm(cfg),
    }


def _maybe_inject_vision(x, vision_embeds, cfg: ModelConfig):
    if vision_embeds is None or cfg.n_vision_tokens == 0:
        return x
    n = vision_embeds.shape[1]
    return jnp.concatenate(
        [vision_embeds.astype(x.dtype), x[:, n:, :]], axis=1)


def lm_forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig, *,
               vision_embeds: Optional[jnp.ndarray] = None,
               remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) -> (logits (B,S,V), aux_loss)."""
    x = embed(params["embed"], tokens, cfg.compute_dtype)
    x = _maybe_inject_vision(x, vision_embeds, cfg)
    # sequence-sharded residual stream (Megatron-SP): the scan carry is
    # saved per layer by remat, so sharding it over `model` divides the
    # dominant training-memory term by the TP width.
    x = constrain(x, "batch", "seq", None)

    def body(carry, layer_params):
        xx, aux = carry
        xx, a = block_forward(layer_params, xx, cfg)
        xx = constrain(xx, "batch", "seq", None)
        return (xx, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = layer_scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embed"], x, cfg), aux


def lm_prefill_batched(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                       vision_embeds: Optional[jnp.ndarray] = None,
                       last_pos: Optional[jnp.ndarray] = None):
    """Serving prefill: full-sequence pass that RETURNS the KV cache and
    only the last-position logits (llama.cpp semantics).  Attention-free
    families return logits only (their state is O(1) and rebuilt by the
    engine).

    ``last_pos`` (B,) selects which position's logits to return; it lets
    the engine right-pad prompts to a shape bucket (causal attention
    keeps positions < last_pos untouched by the padding) so prompt
    lengths stop forcing one XLA compile each."""
    x = embed(params["embed"], tokens, cfg.compute_dtype)
    x = _maybe_inject_vision(x, vision_embeds, cfg)
    x = constrain(x, "batch", "seq", None)
    has_attn = cfg.family != "ssm"

    def body(xx, layer_params):
        h = _sp_in(apply_norm(layer_params["norm1"], xx, cfg.norm))
        if cfg.family == "ssm":
            from repro.models.ssm import mamba2_forward
            return xx + _sp_out(
                mamba2_forward(layer_params["ssm"], h, cfg)), None
        att, kv = attention_forward(layer_params["attn"], h, cfg,
                                    return_kv=True)
        if cfg.family == "hybrid":
            from repro.models.ssm import mamba2_forward
            ssm = mamba2_forward(layer_params["ssm"], h, cfg)
            xx = xx + _sp_out(0.5 * (att + ssm))
        else:
            xx = xx + _sp_out(att)
        h2 = _sp_in(apply_norm(layer_params["norm2"], xx, cfg.norm))
        if cfg.family == "moe":
            mout, _ = moe_forward(layer_params["moe"], h2, cfg.moe)
            if cfg.moe.dense_residual:
                mout = mout + swiglu(layer_params["mlp"], h2)
            xx = xx + _sp_out(mout)
        else:
            xx = xx + _sp_out(swiglu(layer_params["mlp"], h2))
        return xx, kv

    x, kv = layer_scan(body, x, params["blocks"])
    x = apply_norm(params["final_norm"], x, cfg.norm)
    if last_pos is None:
        x_last = x[:, -1]
    else:
        x_last = jnp.take_along_axis(
            x, last_pos.astype(jnp.int32)[:, None, None], axis=1)[:, 0]
    logits = lm_logits(params["embed"], x_last, cfg)
    return (logits, kv) if has_attn else (logits, None)


def lm_loss(params: Params, batch: Dict[str, jnp.ndarray],
            cfg: ModelConfig, *, remat: bool = False) -> jnp.ndarray:
    logits, aux = lm_forward(params, batch["tokens"], cfg,
                             vision_embeds=batch.get("vision_embeds"),
                             remat=remat)
    mask = batch.get("loss_mask")
    if mask is None and cfg.n_vision_tokens:
        mask = (jnp.arange(batch["tokens"].shape[1])[None, :]
                >= cfg.n_vision_tokens).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, batch["tokens"].shape)
    return cross_entropy(logits, batch["labels"], mask) + aux


# ----------------------------------------------------------------------
# KV / state cache + decode
# ----------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Per-family decode cache, stacked over layers."""
    L = cfg.n_layers
    cache: Params = {"len": jnp.zeros((batch,), jnp.int32)}
    if cfg.family != "ssm":
        win = cfg.sliding_window
        s = min(max_len, win) if win else max_len
        kv_shape = (L, batch, cfg.n_kv_heads, s, cfg.hd)
        if cfg.kv_quant == "int8":
            cache["k"] = jnp.zeros(kv_shape, jnp.int8)
            cache["v"] = jnp.zeros(kv_shape, jnp.int8)
            sc_shape = (L, batch, cfg.n_kv_heads, s, 1)
            cache["k_scale"] = jnp.ones(sc_shape, jnp.float32)
            cache["v_scale"] = jnp.ones(sc_shape, jnp.float32)
        else:
            cache["k"] = jnp.zeros(kv_shape, cfg.compute_dtype)
            cache["v"] = jnp.zeros(kv_shape, cfg.compute_dtype)
    if cfg.family in ("ssm", "hybrid"):
        st = init_mamba2_state(cfg, batch)
        cache["ssm_h"] = jnp.broadcast_to(
            st["h"][None], (L,) + st["h"].shape).copy()
        cache["ssm_conv"] = jnp.broadcast_to(
            st["conv"][None], (L,) + st["conv"].shape).copy()
    return cache


#: cache keys that are NOT stacked per layer: ``len`` is per-lane
#: metadata; ``block_tables`` names pool pages shared by every layer.
CACHE_SHARED_KEYS = ("len", "block_tables")

#: the paged cache's page pools: stacked per layer, but read and written
#: in place by the decode op at a layer index, never sliced per layer
PAGED_POOL_KEYS = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages")


def paged_capacity(max_len: int, cfg: ModelConfig) -> int:
    """Positions one lane's block table must back: the window if the
    config slides, else the full context."""
    win = cfg.sliding_window
    return min(max_len, win) if win else max_len


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int = 16,
                     n_pages: Optional[int] = None) -> Params:
    """Paged decode cache: a global page pool plus per-lane block tables.

    Layout (vs :func:`init_cache`'s dense ``(L, B, Hkv, smax, D)``):

    * ``k_pages``/``v_pages``: ``(L, P, Hkv, ps, D)`` -- one pool shared
      by all lanes; a physical page holds ``ps`` consecutive positions
      of ONE lane (all layers use the same page id for a given logical
      page, so the table is per-lane, not per-layer);
    * ``block_tables``: ``(B, T)`` int32, lane's physical page ids in
      logical order (``T = capacity/ps``); rides the scan carry next to
      ``len``, un-sliced by the layer loop;
    * int8 adds ``k_scale_pages``/``v_scale_pages`` ``(L, P, Hkv, ps)``
      per-token scales (same quantization as the dense int8 cache, whose
      scales keep a trailing unit axis).

    ``n_pages`` defaults to dense-equivalent capacity
    (``batch * T``); a SERVING caller passes fewer lanes' worth and
    admission becomes proportional to live KV bytes instead of lanes.
    SSM/hybrid recurrent state is O(1) per lane and stays dense.

    Block tables initialize to page 0 for every lane: the CALLER owns
    the lane -> page mapping and must assign disjoint pages before
    decoding more than one lane (``ServeEngine`` additionally keeps a
    scratch page for dead lanes, whose frozen-slot writes would
    otherwise land on re-issued pages).
    """
    L = cfg.n_layers
    cache: Params = {"len": jnp.zeros((batch,), jnp.int32)}
    if cfg.family != "ssm":
        s = paged_capacity(max_len, cfg)
        assert s % page_size == 0, (
            f"page_size {page_size} must divide cache capacity {s}")
        bt_width = s // page_size
        if n_pages is None:
            n_pages = batch * bt_width
        cache["block_tables"] = jnp.zeros((batch, bt_width), jnp.int32)
        kv_shape = (L, n_pages, cfg.n_kv_heads, page_size, cfg.hd)
        if cfg.kv_quant == "int8":
            cache["k_pages"] = jnp.zeros(kv_shape, jnp.int8)
            cache["v_pages"] = jnp.zeros(kv_shape, jnp.int8)
            sc_shape = (L, n_pages, cfg.n_kv_heads, page_size)
            cache["k_scale_pages"] = jnp.ones(sc_shape, jnp.float32)
            cache["v_scale_pages"] = jnp.ones(sc_shape, jnp.float32)
        else:
            cache["k_pages"] = jnp.zeros(kv_shape, cfg.compute_dtype)
            cache["v_pages"] = jnp.zeros(kv_shape, cfg.compute_dtype)
    if cfg.family in ("ssm", "hybrid"):
        st = init_mamba2_state(cfg, batch)
        cache["ssm_h"] = jnp.broadcast_to(
            st["h"][None], (L,) + st["h"].shape).copy()
        cache["ssm_conv"] = jnp.broadcast_to(
            st["conv"][None], (L,) + st["conv"].shape).copy()
    return cache


def _attn_decode(p, h, cfg, layer_cache, cache_len, new_cache,
                 attn_key="attn", block_tables=None, layer=None):
    """Run cached attention, handling the quantized-KV and paged layouts.
    A paged ``layer_cache`` holds the whole stacked pools, which the
    decode op reads and writes at ``layer``."""
    if block_tables is not None:
        pools = {k: layer_cache[k] for k in PAGED_POOL_KEYS
                 if k in layer_cache}
        att, pools = attention_decode_paged(p[attn_key], h, cfg, pools,
                                            block_tables, cache_len, layer)
        new_cache.update(pools)
        return att
    if cfg.kv_quant == "int8":
        att, kc, vc, ks, vs = attention_decode(
            p[attn_key], h, cfg, layer_cache["k"], layer_cache["v"],
            cache_len, layer_cache["k_scale"], layer_cache["v_scale"])
        new_cache.update(k=kc, v=vc, k_scale=ks, v_scale=vs)
    else:
        att, kc, vc = attention_decode(p[attn_key], h, cfg,
                                       layer_cache["k"], layer_cache["v"],
                                       cache_len)
        new_cache.update(k=kc, v=vc)
    return att


def block_decode(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                 layer_cache: Params, cache_len, block_tables=None,
                 layer=None) -> Tuple[jnp.ndarray, Params]:
    """One-token decode through one block. x: (B, 1, d).

    ``block_tables`` (B, T) selects the paged-attention path, whose
    pools in ``layer_cache`` are the whole stack and ``layer`` this
    block's index in it; the dense per-lane cache path is the pinned
    parity reference.
    """
    new_cache = dict(layer_cache)
    h = apply_norm(p["norm1"], x, cfg.norm)
    if cfg.family == "ssm":
        y, st = mamba2_decode(p["ssm"], h, cfg,
                              {"h": layer_cache["ssm_h"],
                               "conv": layer_cache["ssm_conv"]})
        new_cache.update(ssm_h=st["h"], ssm_conv=st["conv"])
        return x + y, new_cache
    if cfg.family == "hybrid":
        att = _attn_decode(p, h, cfg, layer_cache, cache_len, new_cache,
                           block_tables=block_tables, layer=layer)
        ssm, st = mamba2_decode(p["ssm"], h, cfg,
                                {"h": layer_cache["ssm_h"],
                                 "conv": layer_cache["ssm_conv"]})
        new_cache.update(ssm_h=st["h"], ssm_conv=st["conv"])
        x = x + 0.5 * (att + ssm)
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        return x + swiglu(p["mlp"], h2), new_cache
    att = _attn_decode(p, h, cfg, layer_cache, cache_len, new_cache,
                       block_tables=block_tables, layer=layer)
    x = x + att
    h2 = apply_norm(p["norm2"], x, cfg.norm)
    if cfg.family == "moe":
        mout, _ = moe_forward(p["moe"], h2, cfg.moe)
        if cfg.moe.dense_residual:
            mout = mout + swiglu(p["mlp"], h2)
        return x + mout, new_cache
    return x + swiglu(p["mlp"], h2), new_cache


def lm_decode_step(params: Params, cfg: ModelConfig, cache: Params,
                   tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """tokens: (B,) -> (logits (B, V), updated cache).

    The stacked (L, ...) cache rides the scan CARRY (not xs/ys): XLA
    aliases while-loop carries in place, so the multi-GB KV cache is
    updated without the double buffering a scan-output cache would cost.
    The paged pools ride it whole: each layer's decode op reads and
    writes them at the layer index, in the layout they arrive in, so no
    layer of the pool is ever sliced out, copied or put back.  The dense
    per-lane cache and recurrent state, small or the parity reference,
    are dynamic-sliced per layer and written back at its index.  A paged
    cache carries its ``block_tables`` un-sliced next to ``len`` (the
    table is per-lane, shared by every layer).
    """
    x = embed(params["embed"], tokens[:, None], cfg.compute_dtype)
    cache_len = cache["len"]
    block_tables = cache.get("block_tables")
    pools = {k: cache[k] for k in PAGED_POOL_KEYS if k in cache}
    stack = {k: cache[k] for k in cache
             if k not in CACHE_SHARED_KEYS and k not in pools}

    def body(carry, inp):
        x, stack, pools = carry
        layer_params, i = inp
        layer_cache = {
            k: jax.lax.dynamic_index_in_dim(stack[k], i, 0, keepdims=False)
            for k in stack}
        layer_cache.update(pools)
        x, new_lc = block_decode(layer_params, x, cfg, layer_cache,
                                 cache_len, block_tables=block_tables,
                                 layer=i)
        stack = {
            k: jax.lax.dynamic_update_index_in_dim(stack[k], new_lc[k], i, 0)
            for k in stack}
        pools = {k: new_lc[k] for k in pools}
        return (x, stack, pools), None

    (x, stack, pools), _ = layer_scan(
        body, (x, stack, pools),
        (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params["embed"], x[:, 0], cfg)
    new_cache = dict(stack)
    new_cache.update(pools)
    new_cache["len"] = cache_len + 1
    if block_tables is not None:
        new_cache["block_tables"] = block_tables
    return logits, new_cache


def sample_tokens(logits: jnp.ndarray, rng, temperature: float
                  ) -> jnp.ndarray:
    """On-device greedy/temperature sampling. logits (B, V) -> (B,) int32.

    Lives next to the decode step so the logits tensor never leaves the
    device: the serving engine's per-token host round-trip (device->host
    logits copy + numpy argmax/categorical) collapses into the jitted
    step."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        rng, logits / temperature, axis=-1).astype(jnp.int32)


def sample_tokens_lanes(logits: jnp.ndarray, keys: jnp.ndarray,
                        temperature: float) -> jnp.ndarray:
    """Per-lane-keyed sampling: logits (B, V), keys (B,) of PRNG keys.

    Each lane draws with its own key, so a request's sampled stream is a
    pure function of (its key lineage, its token index) -- independent
    of lane neighbors, admission timing, and dispatch granularity."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(
        lambda k, l: jax.random.categorical(k, l / temperature))(
            keys, logits).astype(jnp.int32)


def lm_decode_sample_step(params: Params, cfg: ModelConfig, cache: Params,
                          tokens: jnp.ndarray, rng, *,
                          temperature: float = 0.0
                          ) -> Tuple[jnp.ndarray, Params]:
    """Fused decode step: advance one token AND sample the next, all on
    device. tokens (B,) -> (sampled (B,) int32, updated cache)."""
    logits, cache = lm_decode_step(params, cfg, cache, tokens)
    return sample_tokens(logits, rng, temperature), cache


def lm_decode_n_steps(params: Params, cfg: ModelConfig, cache: Params,
                      tokens: jnp.ndarray, rng, remaining: jnp.ndarray,
                      lane_seed: jnp.ndarray, tok_idx: jnp.ndarray, *,
                      n_steps: int, temperature: float = 0.0,
                      len_cap: int = 0, step_fn=None):
    """Advance every lane ``n_steps`` tokens in ONE host dispatch.

    A ``jax.lax.scan`` over the fused decode+sample step; tokens and
    validity flags accumulate on device and are drained by the caller in
    a single host transfer.  Each lane samples with key
    ``fold_in(fold_in(rng, lane_seed), tok_idx)`` -- ``lane_seed`` is
    the request's admission index, ``tok_idx`` its generated-token count
    -- so a request's stream is a pure function of its own identity:
    invariant to dispatch granularity, admission timing, and lane
    neighbors.

    ``remaining`` (B,) int32 is each lane's generation budget; exhausted
    lanes keep stepping (their KV writes land in a lane that will be
    re-prefilled on admission) but their samples are flagged invalid,
    their token index stops advancing, and their cache length is frozen
    (so the length-aware kernel does not stream a retired context).
    ``len_cap`` > 0 zeroes the budget once the cache length reaches it
    (the engine passes ``max_len - 1``).

    Returns (tokens (n, B), valid (n, B) bool, next_tokens (B,), cache,
    remaining, tok_idx).
    """
    if step_fn is None:
        step_fn = functools.partial(lm_decode_step, params, cfg)
    lane_keys = jax.vmap(lambda s: jax.random.fold_in(rng, s))(lane_seed)

    def body(carry, _):
        cache, tok, rem, idx = carry
        live = rem > 0
        len_before = cache["len"]
        logits, cache = step_fn(cache, tok)
        cache["len"] = jnp.where(live, cache["len"], len_before)
        keys = jax.vmap(jax.random.fold_in)(lane_keys, idx)
        nxt = sample_tokens_lanes(logits, keys, temperature)
        rem = jnp.where(live, rem - 1, 0)
        if len_cap > 0:
            rem = jnp.where(cache["len"] >= len_cap, 0, rem)
        idx = idx + live.astype(jnp.int32)
        return (cache, nxt, rem, idx), (nxt, live)

    (cache, tok, remaining, tok_idx), (toks, valid) = jax.lax.scan(
        body, (cache, tokens, remaining, tok_idx), None, length=n_steps)
    return toks, valid, tok, cache, remaining, tok_idx


def lm_prefill(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
               max_len: int,
               vision_embeds: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Params]:
    """Build a decode cache by streaming the prompt one token at a time.

    Functional but deliberately simple -- the serving engine
    (``repro.serving``) uses the batched flash path for long prompts and
    falls back to this for correctness tests.
    """
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len)

    def step(cache, t):
        logits, cache = lm_decode_step(params, cfg, cache, t)
        return cache, logits

    cache, logits = jax.lax.scan(step, cache, tokens.T)
    return logits[-1], cache
