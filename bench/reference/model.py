"""Plain float32 forward pass of a decoder-only configuration.

Straight ``jax.numpy``: no kernels, no cache, no pages, no batching
tricks.  Every matrix product runs at ``precision="highest"``, so a
float32 product on a TPU is not cut to one bfloat16 pass.  It follows
the published architecture (pre-norm blocks, RoPE, SwiGLU, tied or
separate output head) and reads the weights the benchmark made
(``harness/weights.py``), in the layout the serving engine takes them;
it imports nothing of the program.  Each layer's weights are cast to
float32 inside the scan over layers, so weights stored in a narrower
type are widened one layer at a time.

A ``deepseek_v3`` configuration (``spec.mla`` and ``spec.moe`` set)
follows the publisher's equations:

- latent attention in its explicit per-head form: q from the hidden
  state; the latent ``c`` and one RoPE key ``k_pe`` (shared by all
  heads) from ``wkv_a``; ``c`` RMS-normed (at ``kv_norm_eps``) and
  decompressed per head by ``wkv_b`` into ``k_nope`` and ``v``; RoPE on
  the rope dims of q and k in DeepSeek's interleaved pairing; scores
  scaled by ``(dn + dr) ** -0.5``;
- routing: sigmoid scores over all published experts, the top k chosen
  on score + ``router_bias``, their weights taken from the unbiased
  scores, normalised to sum 1 (for k > 1) and times
  ``routed_scaling_factor``; the shared experts are always computed;
- the leading ``first_k_dense_replace`` layers (``dense_blocks``) have a
  SwiGLU MLP in place of the experts.

The one departure is the expert share (``model-configs`` guide, section
4): only the experts held here (ids ``first_expert`` on) are computed,
and what the others would add is left out, as in the program.

``operand`` rounds both operands of every product before it is taken,
the router's included.  ``None`` is the reference; ``"float8_e4m3fn"``
is the control, the precision one step below the configuration's
bfloat16: each operand is scaled by its own largest magnitude onto the
float8 range, rounded, and scaled back (per-tensor dynamic scaling), and
the product accumulates in float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Spec(NamedTuple):
    """The sizes and choices the forward pass needs (hashable, so a
    compiled forward is kept per configuration).  ``mla`` and ``moe``
    are the configuration's records (``harness.model_config.MLA`` /
    ``MoE``), ``None`` for a dense one."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    norm: str
    norm_eps: float
    qkv_bias: bool
    rope_theta: float
    tie_embeddings: bool
    vocab_size: int
    rope: str = "rotate_half"
    mla: Optional[object] = None
    moe: Optional[object] = None


def spec_of(arch) -> Spec:
    return Spec(arch.n_heads, arch.n_kv_heads, arch.head_dim, arch.norm,
                arch.norm_eps, arch.qkv_bias, arch.rope_theta,
                arch.tie_embeddings, arch.vocab_size, arch.rope, arch.mla,
                arch.moe)


def _f32(tree):
    return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), tree)


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


def _rope(x, pos, theta, pairing):
    """x (S, H, D): dims i and i + D/2 rotated together (rotate-half), or
    dims 2i and 2i + 1 (interleaved), by pos * theta ** (-2i / D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if pairing == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _softmax_attend(q, k, v, scale, operand):
    """Causal attention of q (S, H, Dk) over k (S, H, Dk) and v (S, H,
    Dv) -> (S, H Dv)."""
    S = q.shape[0]
    pos = jnp.arange(S)
    s = _mm("qhd,khd->hqk", q, k, operand) * scale
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return _mm("hqk,khd->qhd", w, v, operand).reshape(S, -1)


def _gqa(h, a, spec, operand):
    S = h.shape[0]
    H, Hkv, D = spec.n_heads, spec.n_kv_heads, spec.head_dim
    pos = jnp.arange(S)
    q = _mm("sd,dh->sh", h, a["wq"], operand)
    k = _mm("sd,dh->sh", h, a["wk"], operand)
    v = _mm("sd,dh->sh", h, a["wv"], operand)
    if spec.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, H, D), pos, spec.rope_theta, spec.rope)
    k = _rope(k.reshape(S, Hkv, D), pos, spec.rope_theta, spec.rope)
    v = v.reshape(S, Hkv, D)
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    o = _softmax_attend(q, k, v, D ** -0.5, operand)
    return _mm("sh,hd->sd", o, a["wo"], operand)


def _mla(h, a, spec, operand):
    """Latent attention in its explicit per-head form."""
    S, H, m = h.shape[0], spec.n_heads, spec.mla
    r, dn, dr, dv = (m.kv_lora_rank, m.qk_nope_head_dim,
                     m.qk_rope_head_dim, m.v_head_dim)
    pos = jnp.arange(S)
    q = _mm("sd,dh->sh", h, a["wq"], operand).reshape(S, H, dn + dr)
    ckv = _mm("sd,dc->sc", h, a["wkv_a"], operand)
    c = _norm(ckv[:, :r], a["kv_norm"], "rmsnorm", m.kv_norm_eps)
    k_pe = _rope(ckv[:, None, r:], pos, spec.rope_theta, spec.rope)
    kv = _mm("sr,rh->sh", c, a["wkv_b"], operand).reshape(S, H, dn + dv)
    q = jnp.concatenate(
        [q[..., :dn], _rope(q[..., dn:], pos, spec.rope_theta, spec.rope)],
        -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    o = _softmax_attend(q, k, kv[..., dn:], (dn + dr) ** -0.5, operand)
    return _mm("sh,hd->sd", o, a["wo"], operand)


def _swiglu(h, m, operand):
    gate = _mm("sd,df->sf", h, m["w_gate"], operand)
    up = _mm("sd,df->sf", h, m["w_up"], operand)
    return _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"], operand)


def _routed(h, m, spec, operand):
    """What the experts held here add for each token of h (S, d): the
    router over all published experts, then each held expert over every
    token, weighted by that token's routing weight for it (0 where the
    token did not choose it)."""
    e = spec.moe
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, m["router"], operand))
    _, idx = jax.lax.top_k(scores + m["router_bias"],
                           e.num_experts_per_tok)           # (S, k)
    w = jnp.take_along_axis(scores, idx, -1)
    if e.norm_topk_prob and e.num_experts_per_tok > 1:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * e.routed_scaling_factor
    held = e.first_expert + jnp.arange(e.experts_held)
    comb = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)                                  # (S, held)
    gate = _mm("sd,edf->esf", h, m["w_gate"], operand)
    up = _mm("sd,edf->esf", h, m["w_up"], operand)
    out = _mm("esf,efd->esd", jax.nn.silu(gate) * up, m["w_down"], operand)
    return _mm("se,esd->sd", comb, out, operand)


def _block(x, p, spec, operand):
    p = _f32(p)
    h = _norm(x, p.get("norm1", {}), spec.norm, spec.norm_eps)
    attend = _gqa if spec.mla is None else _mla
    x = x + attend(h, p["attn"], spec, operand)
    h = _norm(x, p.get("norm2", {}), spec.norm, spec.norm_eps)
    if "moe" in p:
        return x + _routed(h, p["moe"], spec, operand) \
            + _swiglu(h, p["moe"]["shared"], operand)
    return x + _swiglu(h, p["mlp"], operand)


@functools.partial(jax.jit, static_argnames=("spec", "operand"))
def logits_at(params, tokens, positions, *, spec: Spec, operand=None):
    """Logits over the published vocabulary at ``positions`` of one
    sequence.  tokens (S,) int32; positions (P,) int32 -> (P, V) f32.
    Tokens after the last scored position do not change the result
    (causal), so callers pad ``tokens`` to a fixed length."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def body(x, p):
        return _block(x, p, spec, operand), None

    for stack in ("dense_blocks", "blocks"):
        if stack in params:
            x, _ = jax.lax.scan(body, x, params[stack])
    x = _norm(x[positions], _f32(params["final_norm"]), spec.norm,
              spec.norm_eps)
    if spec.tie_embeddings:
        w = params["embed"]["tok"][:spec.vocab_size].T
    else:
        w = params["embed"]["head"][:, :spec.vocab_size]
    return _mm("pd,dv->pv", x, w.astype(jnp.float32), operand)
