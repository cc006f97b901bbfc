"""Decode attention as the model calls it.

The paged ops pick their kernel by platform when they are lowered
(``jax.lax.platform_dependent``): the block-table Pallas kernel on TPU,
the jnp oracle everywhere else.  No flag selects between them, so the
CPU tests run the oracle and the chip runs the kernel; the Pallas
kernels themselves are tested against the oracles in interpret mode.

The dense-cache ops stay on the jnp oracle on every platform.  The
fixed-lane dense cache is the parity reference, not the served layout,
and Whisper's cross-attention reads a 1500-key encoder cache, which is
not a multiple of the dense kernel's key block.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import (
    decode_attention_paged_pallas, decode_attention_paged_q8_pallas)
from repro.kernels.decode_attention.ref import (
    decode_attention_paged_q8_ref, decode_attention_paged_ref,
    decode_attention_q8_ref, decode_attention_ref)


@jax.jit
def decode_attention(q, k, v, kv_lengths):
    return decode_attention_ref(q, k, v, kv_lengths)


@functools.partial(jax.jit, static_argnames=("qblock",))
def decode_attention_q8(q, k_q, k_scale, v_q, v_scale, kv_lengths, *,
                        qblock: int = 32):
    return decode_attention_q8_ref(q, k_q, k_scale, v_q, v_scale, kv_lengths,
                                   qblock=qblock)


@jax.jit
def decode_attention_paged(q, k_pages, v_pages, block_tables, kv_lengths):
    """Block-table decode attention over a global page pool.

    On TPU the Pallas kernel walks each lane's live pages only;
    elsewhere the jnp oracle gathers the pages first.
    """
    return jax.lax.platform_dependent(
        q, k_pages, v_pages, block_tables, kv_lengths,
        tpu=decode_attention_paged_pallas,
        default=decode_attention_paged_ref)


@functools.partial(jax.jit, static_argnames=("qblock",))
def decode_attention_paged_q8(q, k_pages, k_scale_pages, v_pages,
                              v_scale_pages, block_tables, kv_lengths, *,
                              qblock: int = 32):
    return jax.lax.platform_dependent(
        q, k_pages, k_scale_pages, v_pages, v_scale_pages, block_tables,
        kv_lengths,
        tpu=functools.partial(decode_attention_paged_q8_pallas,
                              qblock=qblock),
        default=functools.partial(decode_attention_paged_q8_ref,
                                  qblock=qblock))
