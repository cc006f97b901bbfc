"""Plain float32 forward pass of a dense decoder-only configuration.

Straight ``jax.numpy``: no kernels, no cache, no pages, no batching
tricks.  Every matrix product runs at ``precision="highest"``, so a
float32 product on a TPU is not cut to one bfloat16 pass.  It follows
the published architecture (pre-norm blocks, rotate-half RoPE, SwiGLU,
tied or separate output head) and reads the weights the benchmark made
(``harness/weights.py``), in the layout the serving engine takes them;
it imports nothing of the program.

``operand`` rounds both operands of every product before it is taken.
``None`` is the reference; ``"float8_e4m3fn"`` is the control, the
precision one step below the configuration's bfloat16: each operand is
scaled by its own largest magnitude onto the float8 range, rounded, and
scaled back (per-tensor dynamic scaling), and the product accumulates in
float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Spec(NamedTuple):
    """The sizes and choices the forward pass needs (hashable, so a
    compiled forward is kept per configuration)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    norm: str
    norm_eps: float
    qkv_bias: bool
    rope_theta: float
    tie_embeddings: bool
    vocab_size: int


def spec_of(arch) -> Spec:
    return Spec(arch.n_heads, arch.n_kv_heads, arch.head_dim, arch.norm,
                arch.norm_eps, arch.qkv_bias, arch.rope_theta,
                arch.tie_embeddings, arch.vocab_size)


def _round(x, operand):
    if operand is None:
        return x
    dt = jnp.dtype(operand)
    top = float(jnp.finfo(dt).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dt).astype(jnp.float32) / scale


def _mm(eq, a, b, operand):
    return jnp.einsum(eq, _round(a, operand), _round(b, operand),
                      precision=HIGHEST)


def _norm(x, p, kind, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta):
    """x (S, H, D), rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _block(x, p, spec, operand):
    S = x.shape[0]
    H, Hkv, D = spec.n_heads, spec.n_kv_heads, spec.head_dim
    pos = jnp.arange(S)
    h = _norm(x, p.get("norm1", {}), spec.norm, spec.norm_eps)
    a = p["attn"]
    q = _mm("sd,dh->sh", h, a["wq"], operand)
    k = _mm("sd,dh->sh", h, a["wk"], operand)
    v = _mm("sd,dh->sh", h, a["wv"], operand)
    if spec.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, H, D), pos, spec.rope_theta)
    k = _rope(k.reshape(S, Hkv, D), pos, spec.rope_theta)
    v = v.reshape(S, Hkv, D)
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = _mm("qhd,khd->hqk", q, k, operand) * D ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("hqk,khd->qhd", w, v, operand).reshape(S, H * D)
    x = x + _mm("sh,hd->sd", o, a["wo"], operand)
    h = _norm(x, p.get("norm2", {}), spec.norm, spec.norm_eps)
    m = p["mlp"]
    gate = _mm("sd,df->sf", h, m["w_gate"], operand)
    up = _mm("sd,df->sf", h, m["w_up"], operand)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"], operand)


@functools.partial(jax.jit, static_argnames=("spec", "operand"))
def logits_at(params, tokens, positions, *, spec: Spec, operand=None):
    """Logits over the published vocabulary at ``positions`` of one
    sequence.  tokens (S,) int32; positions (P,) int32 -> (P, V) f32.
    Tokens after the last scored position do not change the result
    (causal), so callers pad ``tokens`` to a fixed length."""
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda t: t.astype(jnp.float32))
    params = f32(params)
    x = params["embed"]["tok"][tokens]

    def body(x, p):
        return _block(x, p, spec, operand), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _norm(x[positions], params["final_norm"], spec.norm, spec.norm_eps)
    if spec.tie_embeddings:
        w = params["embed"]["tok"][:spec.vocab_size].T
    else:
        w = params["embed"]["head"][:, :spec.vocab_size]
    return _mm("pd,dv->pv", x, w, operand)
