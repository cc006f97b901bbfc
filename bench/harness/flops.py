"""Operations and bytes the algorithm needs, from shapes and lengths.

These count the work a step must do, whatever implements it: a kernel
that reads a page twice, or computes a masked position, does not earn
more.  ``arch`` is a :class:`harness.model_config.Arch`.
"""

from __future__ import annotations

import jax.numpy as jnp


def matmul_params_per_token(arch) -> int:
    """Weights every token multiplies through: the q/k/v/o projections
    and the SwiGLU MLP of each layer, plus the output head over the
    published vocabulary."""
    d, hd = arch.d_model, arch.head_dim
    per_layer = (d * (arch.n_heads + 2 * arch.n_kv_heads) * hd
                 + arch.n_heads * hd * d + 3 * d * arch.d_ff)
    return arch.n_layers * per_layer + d * arch.vocab_size


def attention_flops(arch, kv_len: int) -> int:
    """One query token against ``kv_len`` keys, all layers: q.k and
    p.v, 2 operations per multiply-add each."""
    return 4 * arch.n_layers * arch.n_heads * arch.head_dim * int(kv_len)


def decode_token_flops(arch, kv_len: int) -> int:
    """One decode token of one lane whose cache holds ``kv_len`` keys
    (the new token's own key included)."""
    return 2 * matmul_params_per_token(arch) + attention_flops(arch, kv_len)


def prefill_flops(arch, n_tokens: int) -> int:
    """A causal prefill of ``n_tokens``: the matmuls for every token and
    attention over the lower triangle (keys 1..n for token n)."""
    tri = n_tokens * (n_tokens + 1) // 2
    return (2 * matmul_params_per_token(arch) * n_tokens
            + 4 * arch.n_layers * arch.n_heads * arch.head_dim * tri)


def paged_attention_cost(arch, kv_len: int, page_size: int):
    """(flops, bytes) of paged decode attention for one lane and one
    step, over all layers.

    bytes: the live K and V pages once (whole pages: the pool is read a
    page at a time), plus the query read and the output written;
    flops: 4 H D len per layer.
    """
    item = jnp.dtype(arch.compute_dtype).itemsize
    pages = -(-int(kv_len) // page_size)
    kv = 2 * pages * page_size * arch.n_kv_heads * arch.head_dim * item
    qo = 2 * arch.n_heads * arch.head_dim * item
    return attention_flops(arch, kv_len), arch.n_layers * (kv + qo)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
