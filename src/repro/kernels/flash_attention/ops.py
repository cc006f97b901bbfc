"""Jitted wrapper for full-sequence attention.

* default        -- blockwise jnp scan: O(Sq x block) memory, the path
  prefill, training and the distributed dry-run lower on every platform
  (GSPMD-shardable, CPU-compilable).  The Pallas flash kernel
  (``flash_attention_pallas``) defines no VJP, so the training forward
  cannot use it, and prefill shares this forward.
* ``naive=True`` -- (Sq x Sk) reference, used only as the oracle in
  kernel tests.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.blockwise import blockwise_attention
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "naive",
                                             "block"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    naive: bool = False, block: int = 512):
    if naive:
        return attention_ref(q, k, v, causal=causal, window=window)
    return blockwise_attention(q, k, v, causal=causal, window=window,
                               block=block)
