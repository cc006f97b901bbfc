"""Operations and bytes the algorithm needs, from shapes and lengths.

These count the work a step must do, whatever implements it: a kernel
that reads a page twice, or computes a masked position, does not earn
more.  ``arch`` is a :class:`harness.model_config.Arch`.
"""

from __future__ import annotations

import jax.numpy as jnp


def matmul_params_per_token(arch):
    """Weights every token multiplies through: each layer's attention
    projections and MLP, plus the output head over the published
    vocabulary.

    Dense GQA: q/k/v/o and the SwiGLU MLP.  Latent attention (MLA):
    ``wq + wkv_a + wkv_b + wo`` a layer, in the explicit form and in the
    absorbed decode alike (the absorbed form multiplies ``wkv_b``'s
    k part into q and its v part into the output: the same weights).
    An expert layer: the router, the shared experts, and of the experts
    held here the expected number of local rows under uniform routing,
    ``top_k * experts_held / router_experts`` expert MLPs a token (not
    a whole number in general)."""
    d, hd = arch.d_model, arch.head_dim
    if arch.mla is None:
        per_layer = (d * (arch.n_heads + 2 * arch.n_kv_heads) * hd
                     + arch.n_heads * hd * d + 3 * d * arch.d_ff)
        return arch.n_layers * per_layer + d * arch.vocab_size
    e = arch.moe
    mlp = 3 * d * e.moe_intermediate_size
    expert = (d * e.router_experts + e.n_shared_experts * mlp
              + e.num_experts_per_tok * e.experts_held * mlp
              / e.router_experts)
    n_dense = e.first_k_dense_replace
    return (arch.n_layers * _mla_params(arch) + n_dense * 3 * d * arch.d_ff
            + (arch.n_layers - n_dense) * expert + d * arch.vocab_size)


def _mla_params(arch) -> int:
    d, H, m = arch.d_model, arch.n_heads, arch.mla
    r, dn, dr, dv = (m.kv_lora_rank, m.qk_nope_head_dim,
                     m.qk_rope_head_dim, m.v_head_dim)
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def attention_flops(arch, kv_len: int) -> int:
    """One query token against ``kv_len`` keys, all layers: q.k and
    p.v, 2 operations per multiply-add each.  MLA decodes in the
    absorbed form, over the latent: ``2 H (r + dr)`` for the scores and
    ``2 H r`` for the weighted sum, a position and a layer."""
    if arch.mla is not None:
        m = arch.mla
        r, dr = m.kv_lora_rank, m.qk_rope_head_dim
        return (arch.n_layers * (2 * arch.n_heads * (r + dr)
                                 + 2 * arch.n_heads * r) * int(kv_len))
    return 4 * arch.n_layers * arch.n_heads * arch.head_dim * int(kv_len)


def decode_token_flops(arch, kv_len: int) -> int:
    """One decode token of one lane whose cache holds ``kv_len`` keys
    (the new token's own key included)."""
    return 2 * matmul_params_per_token(arch) + attention_flops(arch, kv_len)


def prefill_flops(arch, n_tokens: int) -> int:
    """A causal prefill of ``n_tokens``: the matmuls for every token and
    attention over the lower triangle (keys 1..n for token n); MLA in
    its per-head form, ``2 H (dn + dr)`` for the scores and ``2 H dv``
    for the weighted sum, a query-key pair and a layer."""
    tri = n_tokens * (n_tokens + 1) // 2
    if arch.mla is not None:
        m = arch.mla
        pair = 2 * arch.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim
                                   + m.v_head_dim)
    else:
        pair = 4 * arch.n_heads * arch.head_dim
    return (2 * matmul_params_per_token(arch) * n_tokens
            + arch.n_layers * pair * tri)


def paged_attention_cost(arch, kv_len: int, page_size: int):
    """(flops, bytes) of paged decode attention for one lane and one
    step, over all layers.

    bytes: the live K and V pages once (whole pages: the pool is read a
    page at a time), plus the query read and the output written;
    flops: 4 H D len per layer.  MLA, absorbed: the live latent pages
    once at ``r + dr`` values a token, the query of ``H (r + dr)`` and
    the latent output of ``H r``; flops as ``attention_flops``.
    """
    item = jnp.dtype(arch.compute_dtype).itemsize
    pages = -(-int(kv_len) // page_size)
    if arch.mla is not None:
        r, dr = arch.mla.kv_lora_rank, arch.mla.qk_rope_head_dim
        kv = pages * page_size * (r + dr) * item
        qo = arch.n_heads * (2 * r + dr) * item
    else:
        kv = 2 * pages * page_size * arch.n_kv_heads * arch.head_dim * item
        qo = 2 * arch.n_heads * arch.head_dim * item
    return attention_flops(arch, kv_len), arch.n_layers * (kv + qo)


def moe_expert_cost(arch, n_tokens: int):
    """(flops, bytes) of one expert layer's held experts over
    ``n_tokens`` tokens, under uniform routing.

    flops: 2 per multiply-add of the SwiGLU expert MLP, over the
    expected local rows ``n_tokens * top_k * held / router_experts``;
    bytes: the held experts expected to be chosen by at least one token,
    ``held * (1 - (1 - top_k / router_experts) ** n_tokens)``, each read
    once in the type the weights are stored in.
    """
    e = arch.moe
    k, E, held = e.num_experts_per_tok, e.router_experts, e.experts_held
    mlp = 3 * arch.d_model * e.moe_intermediate_size
    rows = n_tokens * k * held / E
    hit = held * (1.0 - (1.0 - k / E) ** n_tokens)
    return 2 * mlp * rows, hit * mlp * jnp.dtype(arch.weights_dtype).itemsize


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
