"""Whisper-style encoder-decoder backbone (audio frontend is a stub).

Per the assignment spec, the conv/mel frontend is a STUB: ``input_specs``
provides precomputed frame embeddings (B, S_enc, d_model).  The backbone
is faithful to Whisper's transformer: pre-LN LayerNorm, GELU MLPs,
bidirectional encoder self-attention, causal decoder self-attention plus
cross-attention into the encoder states; learned absolute positions are
replaced by RoPE for shape-agnostic long dry-run cells (noted in
DESIGN.md as a hardware-adaptation simplification).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.models.attention import (attention_decode, attention_forward,
                                    init_attention)
from repro.models.common import (ModelConfig, apply_norm, cross_entropy, layer_scan,
                                 embed, init_embedding, init_norm, lm_logits,
                                 split_keys)
from repro.models.mlp import gelu_mlp, init_gelu_mlp
from repro.parallel.sharding import constrain

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------

def _init_enc_block(key, cfg: ModelConfig) -> Params:
    k1, k2 = split_keys(key, 2)
    return {
        "norm1": init_norm(cfg), "attn": init_attention(k1, cfg),
        "norm2": init_norm(cfg),
        "mlp": init_gelu_mlp(k2, cfg.d_model, cfg.d_ff),
    }


def _init_dec_block(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = split_keys(key, 3)
    return {
        "norm1": init_norm(cfg), "self_attn": init_attention(k1, cfg),
        "norm_x": init_norm(cfg), "cross_attn": init_attention(k2, cfg),
        "norm2": init_norm(cfg),
        "mlp": init_gelu_mlp(k3, cfg.d_model, cfg.d_ff),
    }


def init_whisper(key, cfg: ModelConfig) -> Params:
    kemb, kenc, kdec = split_keys(key, 3)
    enc = [_init_enc_block(jax.random.fold_in(kenc, i), cfg)
           for i in range(cfg.n_encoder_layers)]
    dec = [_init_dec_block(jax.random.fold_in(kdec, i), cfg)
           for i in range(cfg.n_layers)]
    stack = lambda blocks: jax.tree_util.tree_map(  # noqa: E731
        lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": init_embedding(kemb, cfg),
        "encoder": stack(enc),
        "decoder": stack(dec),
        "enc_final_norm": init_norm(cfg),
        "final_norm": init_norm(cfg),
    }


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def encode(params: Params, frames: jnp.ndarray, cfg: ModelConfig,
           remat: bool = False) -> jnp.ndarray:
    """frames: (B, S_enc, d) stub embeddings -> encoder states."""
    x = constrain(frames.astype(cfg.compute_dtype), "batch", None, None)

    def body(x, p):
        h = constrain(apply_norm(p["norm1"], x, cfg.norm),
                      "batch", None, None)
        x = x + constrain(attention_forward(p["attn"], h, cfg, causal=False),
                          "batch", "seq", None)
        h2 = constrain(apply_norm(p["norm2"], x, cfg.norm),
                       "batch", None, None)
        x = constrain(x + gelu_mlp(p["mlp"], h2), "batch", "seq", None)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = layer_scan(body, x, params["encoder"])
    return apply_norm(params["enc_final_norm"], x, cfg.norm)


def _cross_attention(p, x, enc_kv, cfg: ModelConfig):
    """Cross-attn with precomputed encoder K/V: (B, Hkv, S_enc, D)."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(b, s, cfg.n_heads, cfg.hd).transpose(0, 2, 1, 3)
    k, v = enc_kv
    out = flash_attention(q, k, v, causal=False)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))


def _enc_kv(p, enc: jnp.ndarray, cfg: ModelConfig):
    b, s, _ = enc.shape
    k = jnp.einsum("bsd,dh->bsh", enc, p["wk"].astype(enc.dtype))
    v = jnp.einsum("bsd,dh->bsh", enc, p["wv"].astype(enc.dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc.dtype)
        v = v + p["bv"].astype(enc.dtype)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
    return k, v


def decode_forward(params: Params, tokens: jnp.ndarray, enc: jnp.ndarray,
                   cfg: ModelConfig, remat: bool = False) -> jnp.ndarray:
    """Teacher-forced decoder pass -> logits (B, S_dec, V)."""
    x = embed(params["embed"], tokens, cfg.compute_dtype)

    def body(x, p):
        h = constrain(apply_norm(p["norm1"], x, cfg.norm),
                      "batch", None, None)
        x = x + constrain(
            attention_forward(p["self_attn"], h, cfg, causal=True),
            "batch", "seq", None)
        hx = constrain(apply_norm(p["norm_x"], x, cfg.norm),
                       "batch", None, None)
        x = x + constrain(
            _cross_attention(p["cross_attn"], hx,
                             _enc_kv(p["cross_attn"], enc, cfg), cfg),
            "batch", "seq", None)
        h2 = constrain(apply_norm(p["norm2"], x, cfg.norm),
                       "batch", None, None)
        x = constrain(x + gelu_mlp(p["mlp"], h2), "batch", "seq", None)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = layer_scan(body, x, params["decoder"])
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return lm_logits(params["embed"], x, cfg)


def whisper_loss(params: Params, batch: Dict[str, jnp.ndarray],
                 cfg: ModelConfig, remat: bool = False) -> jnp.ndarray:
    enc = encode(params, batch["frames"], cfg, remat=remat)
    logits = decode_forward(params, batch["tokens"], enc, cfg, remat=remat)
    return cross_entropy(logits, batch["labels"], batch.get("loss_mask"))


# ----------------------------------------------------------------------
# cached decode
# ----------------------------------------------------------------------

def init_whisper_cache(params: Params, enc: jnp.ndarray, cfg: ModelConfig,
                       batch: int, max_len: int) -> Params:
    """Self-attn KV ring + precomputed per-layer cross KV."""
    L = cfg.n_layers
    kv_shape = (L, batch, cfg.n_kv_heads, max_len, cfg.hd)

    def per_layer_kv(p):
        return _enc_kv(p, enc, cfg)

    cross_k, cross_v = jax.vmap(
        lambda p: per_layer_kv(p))(
        params["decoder"]["cross_attn"])  # (L, B, Hkv, S_enc, D)
    return {
        "len": jnp.zeros((batch,), jnp.int32),
        "k": jnp.zeros(kv_shape, cfg.compute_dtype),
        "v": jnp.zeros(kv_shape, cfg.compute_dtype),
        "cross_k": cross_k,
        "cross_v": cross_v,
    }


def whisper_decode_step(params: Params, cfg: ModelConfig, cache: Params,
                        tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    if cfg.kv_quant is not None:
        raise NotImplementedError(
            "int8 KV cache is wired for the decoder-only families; "
            "whisper-base caches are small enough in bf16")
    x = embed(params["embed"], tokens[:, None], cfg.compute_dtype)
    cache_len = cache["len"]
    b = tokens.shape[0]
    enc_len = jnp.full((b,), cache["cross_k"].shape[3], jnp.int32)

    def body(carry, xs):
        x, k_all, v_all = carry
        p, ck, cv, i = xs
        k_c = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        v_c = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        h = apply_norm(p["norm1"], x, cfg.norm)
        att, k_c, v_c = attention_decode(p["self_attn"], h, cfg, k_c, v_c,
                                         cache_len)
        x = x + att
        hx = apply_norm(p["norm_x"], x, cfg.norm)
        q = jnp.einsum("bsd,dh->bsh", hx,
                       p["cross_attn"]["wq"].astype(x.dtype))
        if cfg.qkv_bias:
            q = q + p["cross_attn"]["bq"].astype(x.dtype)
        q = q.reshape(b, cfg.n_heads, cfg.hd)
        co = decode_attention(q, ck, cv, enc_len)
        co = co.reshape(b, 1, cfg.n_heads * cfg.hd)
        x = x + jnp.einsum("bsh,hd->bsd", co,
                           p["cross_attn"]["wo"].astype(x.dtype))
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        x = x + gelu_mlp(p["mlp"], h2)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, k_c, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, v_c, i, 0)
        return (x, k_all, v_all), None

    xs = (params["decoder"], cache["cross_k"], cache["cross_v"],
          jnp.arange(cfg.n_layers, dtype=jnp.int32))
    (x, new_k, new_v), _ = layer_scan(body, (x, cache["k"], cache["v"]), xs)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params["embed"], x[:, 0], cfg)
    new_cache = dict(cache)
    new_cache.update(k=new_k, v=new_v, len=cache_len + 1)
    return logits, new_cache
