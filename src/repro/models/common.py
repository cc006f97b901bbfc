"""Shared model substrate: configs, norms, RoPE, embeddings, init.

All models are *functional*: parameters are nested dicts of jnp arrays,
layers are stacked along a leading axis and traversed with
``jax.lax.scan`` so HLO size (and dry-run compile time) is O(1) in depth.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------
# Config
# ----------------------------------------------------------------------

VOCAB_ALIGN = 256  # Megatron convention: pad vocab for clean TP sharding


def pad_vocab(v: int, align: int = VOCAB_ALIGN) -> int:
    return (v + align - 1) // align * align


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert_ff: int
    capacity_factor: float = 1.25
    dense_residual: bool = False   # Arctic: dense FFN in parallel with MoE
    n_shared_experts: int = 0      # Moonlight/DeepSeek style
    router_aux_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    # fraction of d_model given to the SSM branch in hybrid blocks
    d_inner_override: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config covers every family in the pool (see configs/)."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    norm: str = "rmsnorm"          # rmsnorm | layernorm | nonparametric_ln
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    max_seq_len: int = 131072
    sliding_window: Optional[int] = None   # hybrid/hymba local attention
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # encoder-decoder (whisper)
    n_encoder_layers: int = 0
    # vlm: number of prefix vision tokens the stub frontend provides
    n_vision_tokens: int = 0
    dtype: str = "bfloat16"
    # decode KV cache quantization: None | "int8" (per-token-per-head
    # symmetric scales; beyond-paper application of C4)
    kv_quant: Optional[str] = None

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    def active_params_per_layer(self) -> float:
        """Active (per-token) parameter count of one block."""
        d, hd = self.d_model, self.hd
        attn = d * (self.n_heads * hd) * 2 + d * (self.n_kv_heads * hd) * 2
        if self.family == "ssm":
            s = self.ssm or SSMConfig()
            d_in = s.expand * d
            return 2 * d * d_in + d_in * d + d_in * (2 * s.state_dim)
        mlp = 3 * d * self.d_ff
        if self.moe is not None:
            m = self.moe
            mlp = 3 * d * m.d_expert_ff * (m.top_k + m.n_shared_experts)
            if m.dense_residual:
                mlp += 3 * d * self.d_ff
        if self.family == "hybrid":
            s = self.ssm or SSMConfig()
            d_in = s.d_inner_override or (s.expand * d)
            return attn + mlp + 2 * d * d_in + d_in * d
        return attn + mlp

    def active_params(self) -> float:
        body = self.n_layers * self.active_params_per_layer()
        emb = self.d_model * self.padded_vocab
        if not self.tie_embeddings:
            emb *= 2
        return body + emb

    def total_params(self) -> float:
        per = self.active_params_per_layer()
        if self.moe is not None:
            m = self.moe
            d = self.d_model
            per = (per - 3 * d * m.d_expert_ff * (m.top_k + m.n_shared_experts)
                   + 3 * d * m.d_expert_ff * (m.n_experts
                                              + m.n_shared_experts))
        body = self.n_layers * per
        emb = self.d_model * self.padded_vocab * (1 if self.tie_embeddings
                                                  else 2)
        return body + emb


# ----------------------------------------------------------------------
# Initialization
# ----------------------------------------------------------------------

def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (kept f32; cast at use)."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dtype=jnp.float32):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm == "nonparametric_ln":      # OLMo: no learned affine
        return {}
    raise ValueError(cfg.norm)


def apply_norm(params, x: jnp.ndarray, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf / rms * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(
            jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------

def rope_angles(positions: jnp.ndarray, head_dim: int,
                theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (..., S) int -> cos/sin of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, D); cos/sin: (..., S, D//2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    # rotate-half convention (llama / qwen)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
                           ).astype(x.dtype)


# ----------------------------------------------------------------------
# Embedding / LM head
# ----------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    p = {"tok": dense_init(key, (cfg.padded_vocab, cfg.d_model), scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(key, 1),
                               (cfg.d_model, cfg.padded_vocab))
    return p


def embed(params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return params["tok"].astype(dtype)[tokens]


def lm_logits(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Final projection with padded-vocab masking to -inf."""
    if cfg.tie_embeddings:
        w = params["tok"].astype(x.dtype).T
    else:
        w = params["head"].astype(x.dtype)
    logits = jnp.einsum("...d,dv->...v", x, w).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e30)
    return logits


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy in f32. logits (..., V), labels (...).

    The gold logit is extracted with a one-hot einsum rather than
    ``take_along_axis`` so a vocab-sharded logits tensor reduces with a
    psum instead of an all-gather (GSPMD-friendly).
    """
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.einsum("...v,...v->...", logits, onehot)
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


@jax.custom_jvp
def _scan_barrier(tree):
    """`optimization_barrier` with an identity differentiation rule.

    `optimization_barrier` has no JVP registered, so routing the scan
    carry through it raw breaks `jax.grad` over any scanned model.  The
    barrier only constrains *scheduling*; its tangent map is the
    identity, so the custom rule passes tangents straight through while
    the primal keeps pinning the weight all-gather inside the loop.
    """
    return jax.lax.optimization_barrier(tree)


@_scan_barrier.defjvp
def _scan_barrier_jvp(primals, tangents):
    (tree,), (dtree,) = primals, tangents
    return _scan_barrier(tree), dtree


def layer_scan(body, carry, xs):
    """lax.scan over stacked layers; fully unrolled when
    REPRO_SCAN_UNROLL=1 (dry-run mode) so XLA cost_analysis counts every
    layer instead of one while-loop body.

    The scanned path threads layer params through an optimization
    barrier tied to the carry, so the SPMD partitioner cannot hoist the
    FSDP weight all-gather out of the loop (which would materialize
    every layer's gathered weights at once -- the praxis/paxml trick).
    """
    unroll = os.environ.get("REPRO_SCAN_UNROLL", "0") == "1"
    if not unroll:
        def barrier_body(c, x):
            c, x = _scan_barrier((c, x))
            return body(c, x)
        return jax.lax.scan(barrier_body, carry, xs)
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        xi = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
