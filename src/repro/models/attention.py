"""GQA attention with RoPE: training forward + cached decode step."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_paged,
                                                decode_attention_paged_q8)
from repro.kernels.flash_attention.ops import flash_attention
from repro.parallel.sharding import constrain
from repro.models.common import (ModelConfig, apply_rope, dense_init,
                                 rope_angles, split_keys)


def init_attention(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.hd
    kq, kk, kv, ko = split_keys(key, 4)
    p = {
        "wq": dense_init(kq, (d, cfg.n_heads * hd)),
        "wk": dense_init(kk, (d, cfg.n_kv_heads * hd)),
        "wv": dense_init(kv, (d, cfg.n_kv_heads * hd)),
        "wo": dense_init(ko, (cfg.n_heads * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    return p


def _project_qkv(p, x, cfg: ModelConfig):
    b, s, d = x.shape
    hd = cfg.hd
    q = constrain(jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype)),
                  "batch", None, "tp")
    k = constrain(jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype)),
                  "batch", None, "tp")
    v = constrain(jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype)),
                  "batch", None, "tp")
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    return q, k, v


def attention_forward(p, x: jnp.ndarray, cfg: ModelConfig, *,
                      positions: Optional[jnp.ndarray] = None,
                      causal: bool = True,
                      return_kv: bool = False):
    """Full-sequence attention. x: (B, S, d)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    cos, sin = rope_angles(positions, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # sequence parallelism: shard S over the model axis for attention
    qt = constrain(q.transpose(0, 2, 1, 3), "batch", None, "seq", None)
    kt = constrain(k.transpose(0, 2, 1, 3), "batch", None, "seq", None)
    vt = constrain(v.transpose(0, 2, 1, 3), "batch", None, "seq", None)
    out = flash_attention(qt, kt, vt, causal=causal,
                          window=cfg.sliding_window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (kt, vt)
    return out


def quantize_kv_token(k: jnp.ndarray):
    """Per-(token, head) symmetric int8 quantization of one KV vector.
    k: (B, Hkv, D) -> (int8 values, f32 scale (B, Hkv, 1))."""
    scale = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def attention_decode(p, x: jnp.ndarray, cfg: ModelConfig, k_cache, v_cache,
                     cache_len, k_scale=None, v_scale=None):
    """Single-token decode. x: (B, 1, d); caches: (B, Hkv, Smax, D).

    With a sliding-window config the cache is a ring buffer of size
    ``window`` (positions wrap), keeping long-context decode O(window).
    With ``cfg.kv_quant == "int8"`` the caches are int8 with per-token
    f32 scales (k_scale/v_scale: (B, Hkv, Smax, 1)): the dequantize
    fuses into the attention reads, halving decode's dominant HBM term.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg)
    cos, sin = rope_angles(cache_len[:, None], cfg.hd, cfg.rope_theta)
    # single-token tensors are tiny: replicate over the model axis so the
    # softmax conflict resolves by gathering q, never the KV cache.
    q = constrain(apply_rope(q, cos, sin)[:, 0], "batch", None, None)
    k = constrain(apply_rope(k, cos, sin)[:, 0], "batch", None, None)
    v = constrain(v[:, 0], "batch", None, None)
    smax = k_cache.shape[2]
    # uniform ring addressing: slot = position mod capacity.  For a
    # full-context cache positions never wrap (the engine caps length at
    # smax), so the modulo is the identity; for a window cache it IS the
    # rotation -- one formula, no sliding-window special case.
    slot = cache_len % smax

    def put(cache, val, i):
        return jax.vmap(
            lambda c, vv, j: jax.lax.dynamic_update_slice(
                c, vv[:, None, :], (0, j, 0)))(cache, val, i)

    quant = cfg.kv_quant == "int8"
    if quant:
        kq, ks = quantize_kv_token(k)
        vq, vs = quantize_kv_token(v)
        k_cache = put(k_cache, kq, slot)
        v_cache = put(v_cache, vq, slot)
        k_scale = put(k_scale, ks, slot)
        v_scale = put(v_scale, vs, slot)
        # dequantize fused into the attention reads (int8 HBM traffic)
        k_eff = k_cache.astype(jnp.float32) * k_scale
        v_eff = v_cache.astype(jnp.float32) * v_scale
    else:
        k_cache = put(k_cache, k, slot)
        v_cache = put(v_cache, v, slot)
        k_eff, v_eff = k_cache, v_cache
    eff_len = jnp.minimum(cache_len + 1, smax)
    out = decode_attention(q, k_eff, v_eff, eff_len.astype(jnp.int32))
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    if quant:
        return out, k_cache, v_cache, k_scale, v_scale
    return out, k_cache, v_cache


def attention_decode_paged(p, x: jnp.ndarray, cfg: ModelConfig, pools,
                           block_tables, cache_len, layer):
    """Single-token decode against one layer of the stacked paged cache.

    x: (B, 1, d); pools: ``{"k_pages", "v_pages"}`` (L, P, Hkv, ps, D),
    the whole stack over layers, and ``layer`` the int32 index this
    block reads and writes; block_tables: (B, T) physical page ids in
    logical order.  The lane's capacity is ``T*ps`` positions: a
    sliding-window lane owns a FIXED set of pages and rotates through
    them at page granularity (slot = position mod T*ps), so the ring
    write and the block-table gather share one formula with the
    full-context case.  With ``cfg.kv_quant == "int8"`` the value pools
    are int8 beside per-(token, head) f32 scale pools
    ``{"k_scale_pages", "v_scale_pages"}`` (L, P, Hkv, ps), quantized
    exactly like the dense int8 cache.  The decode op writes the new
    key and value into the pools in place and attends over them;
    returns (out, pools).
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg)
    cos, sin = rope_angles(cache_len[:, None], cfg.hd, cfg.rope_theta)
    q = constrain(apply_rope(q, cos, sin)[:, 0], "batch", None, None)
    k = constrain(apply_rope(k, cos, sin)[:, 0], "batch", None, None)
    v = constrain(v[:, 0], "batch", None, None)
    ps = pools["k_pages"].shape[3]
    cap = block_tables.shape[1] * ps     # positions the table can back
    slot = (cache_len % cap).astype(jnp.int32)
    eff_len = jnp.minimum(cache_len + 1, cap).astype(jnp.int32)
    bt = block_tables.astype(jnp.int32)
    if cfg.kv_quant == "int8":
        kq, ks = quantize_kv_token(k)
        vq, vs = quantize_kv_token(v)
        keys = ("k_pages", "k_scale_pages", "v_pages", "v_scale_pages")
        out, *new = decode_attention_paged_q8(
            q, kq, ks[..., 0], vq, vs[..., 0], *(pools[name] for name in keys),
            bt, eff_len, slot, layer)
        pools = dict(zip(keys, new))
    else:
        out, kp, vp = decode_attention_paged(
            q, k, v, pools["k_pages"], pools["v_pages"], bt, eff_len, slot,
            layer)
        pools = {"k_pages": kp, "v_pages": vp}
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    return out, pools
