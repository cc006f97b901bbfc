"""Split-K decode attention Pallas kernel (the paper's key phase, C3).

LLM decode reads one query token against a long KV cache -- *pure
bandwidth*, the workload where the paper shows a mining GPU matching an
A100.  The dense-cache kernels stream the KV cache through VMEM in key
blocks (grid ``(B, H, Sk/bk)``) with a running-softmax state in VMEM
scratch -- i.e. FlashDecoding adapted to the HBM->VMEM hierarchy.

The served kernel, :func:`decode_attention_paged_pallas`, reads one
layer of a global page pool stacked over layers through a block table,
and writes the step's new key and value into it in place.  Its grid is
one step per lane: the pools stay in HBM and the kernel copies the
lane's live pages itself, whole pages of every KV head at a time, in
double-buffered groups of the fewest pages whose K and V bytes reach
512 KiB, and scores every query head of a KV head at once.  Table slots
past a lane's length cost no step and move no byte.

A quantized-KV variant (q8_0 per-32-block scales along the key axis)
halves the cache traffic: the dequantize happens on the VPU right after
the VMEM load, upstream of the (tiny) MXU dots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# One online-softmax accumulation shared by all four kernel bodies
# (masked/length-aware x dense/q8): the variants differ only in how the
# (k, v) tile is materialized and in whether dead blocks are skipped.

def _flash_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _flash_block(q, k, v, kv_len, j, bk: int, acc_ref, m_ref, l_ref):
    """Fold one (bk, d) KV tile into the running softmax state, masking
    positions beyond the live cache length (ragged batches)."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (1, bk)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = k_pos < kv_len
    s = jnp.where(mask, s, _NEG_INF)
    m_prev, l_prev = m_ref[0, 0], l_ref[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (1, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = (l_prev * alpha + jnp.sum(p))[None, None]
    m_ref[...] = m_new[None, None]
    acc_ref[...] = (acc_ref[...] * alpha
                    + jnp.dot(p, v, preferred_element_type=jnp.float32))


def _flash_store(o_ref, acc_ref, l_ref):
    l = l_ref[0, 0]
    l = jnp.where(l == 0.0, 1.0, l)                      # all-dead lane
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _dequant_tile(vals_ref, scale_ref, qblock: int):
    """(bk, d) int8 tile + (bk/qblock, 1) scales -> f32, on the VPU
    straight out of VMEM."""
    return (vals_ref[0, 0].astype(jnp.float32)
            * jnp.repeat(scale_ref[0, 0], qblock, axis=0))


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, bk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (1, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    _flash_block(q, k, v, len_ref[0], j, bk, acc_ref, m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        _flash_store(o_ref, acc_ref, l_ref)


def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            kv_lengths: jnp.ndarray, *, scale=None,
                            bk: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, D); k/v: (B, Hkv, S, D); kv_lengths: (B,) int32."""
    b, h, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    bk = min(bk, sk)
    assert sk % bk == 0
    scale = float(scale if scale is not None else d ** -0.5)
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk)
    q4 = q[:, :, None, :]                                 # (B, H, 1, D)
    return pl.pallas_call(
        kernel,
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, j: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, j: (bb, hh // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, j: (bb, hh // group, j, 0)),
            pl.BlockSpec((1,), lambda bb, hh, j: (bb,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda bb, hh, j: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q4, k, v, kv_lengths)[:, :, 0, :]


# ----------------------------------------------------------------------
# length-aware variant: HBM traffic proportional to live context
# ----------------------------------------------------------------------
#
# The masked kernel above streams all Sk/bk key blocks per lane and
# relies on the softmax mask to drop dead positions -- HBM reads scale
# with max_len.  Here the per-lane lengths are scalar-prefetched
# (available before the kernel body runs), so the k/v BlockSpec index
# maps can clamp the block index to the last LIVE block: once the grid
# walks past ceil(len/bk) blocks, the index map keeps returning the same
# block, and the pipeline skips the DMA for a block it already holds.
# Compute for dead blocks is skipped with pl.when.  Reads scale with the
# live cache length; the masked kernel stays as the parity reference.


def _last_live_block(lens_ref, bb, bk: int):
    """Index of the last block holding live keys for lane ``bb`` (>= 0
    so a length-0 lane still maps to block 0: one block fetched, all
    compute skipped)."""
    n_live = pl.cdiv(lens_ref[bb], bk)
    return jnp.maximum(n_live - 1, 0)


def _decode_la_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                      l_ref, *, scale: float, bk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    kv_len = len_ref[pl.program_id(0)]

    @pl.when(j * bk < kv_len)                  # skip dead blocks entirely
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        _flash_block(q, k, v, kv_len, j, bk, acc_ref, m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        _flash_store(o_ref, acc_ref, l_ref)


def decode_attention_lengthaware_pallas(q: jnp.ndarray, k: jnp.ndarray,
                                        v: jnp.ndarray,
                                        kv_lengths: jnp.ndarray, *,
                                        scale=None, bk: int = 512,
                                        interpret: bool = False
                                        ) -> jnp.ndarray:
    """Length-aware decode attention: same contract as
    :func:`decode_attention_pallas`, but key blocks past the live cache
    length are never fetched from HBM."""
    b, h, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    bk = min(bk, sk)
    assert sk % bk == 0
    scale = float(scale if scale is not None else d ** -0.5)
    kernel = functools.partial(_decode_la_kernel, scale=scale, bk=bk)
    q4 = q[:, :, None, :]

    def kv_index(bb, hh, j, lens_ref):
        jj = jnp.minimum(j, _last_live_block(lens_ref, bb, bk))
        return (bb, hh // group, jj, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d),
                         lambda bb, hh, j, lens_ref: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bb, hh, j, lens_ref: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(kv_lengths.astype(jnp.int32), q4, k, v)[:, :, 0, :]


def kv_blocks_fetched(kv_lengths, sk: int, bk: int = 512):
    """Modeled K-block fetch count per lane for the length-aware kernel.

    A lane of length L DMAs ``max(ceil(L/bk), 1)`` key blocks (a dead
    lane still pins block 0); the masked kernel always fetches ``sk/bk``.
    Returns an int array shaped like ``kv_lengths``.
    """
    import numpy as np
    lens = np.asarray(kv_lengths)
    bk = min(bk, sk)
    return np.maximum(-(-lens // bk), 1).astype(np.int64)


def kv_pages_fetched(kv_lengths, bt_width: int, page_size: int):
    """Modeled page fetch count per lane for a block-table index map.

    Models :func:`decode_attention_paged_q8_pallas`, whose index map is
    clamped to the last live page: a lane of length L DMAs
    ``clip(ceil(L/page_size), 1, bt_width)`` pages (a dead lane still
    pins one) -- identical to :func:`kv_blocks_fetched` when
    ``page_size == bk``, which is the paged-vs-dense bytes/token parity
    the bench pins.  :func:`decode_attention_paged_pallas` fetches the
    same pages: every lane attends at least its own new key
    (``positions < kv_lengths``), so every lane reads at least one page.
    """
    import numpy as np
    lens = np.asarray(kv_lengths)
    return np.clip(-(-lens // page_size), 1, bt_width).astype(np.int64)


# ----------------------------------------------------------------------
# paged (block-table) variants: the KV lives in a global page pool
# ----------------------------------------------------------------------
#
# The length-aware kernels above still address a dense per-lane cache
# (B, Hkv, S, D): capacity is partitioned at allocation time.  Here the
# cache is a page POOL (L, P, Hkv, ps, D) shared by all lanes and
# stacked over layers, and each lane's pages are named by a block table
# (B, T) of physical page ids in logical order.  The layer, the
# per-lane lengths, the new token's positions and the block tables are
# all scalar-prefetched, so a kernel reads one layer of the stack in
# place: no layer slice is ever copied out of it.  The float kernel
# walks a lane's ceil(len/ps) live table slots in a loop of its own and
# copies each page (all KV heads, contiguous in the pool) from HBM
# itself; the q8 kernel's index maps translate the logical page index
# through the table and clamp it to the last live page.  Either way
# pages past ceil(len/ps) are never fetched.  Table slot ``j`` holds
# logical positions [j*ps, (j+1)*ps) -- a sliding-window lane rotates
# pages at the table level (slot = position mod T*ps), which is safe
# because the online softmax is permutation-invariant once every slot
# is live.
#
# Both kernels also WRITE the step's new key and value: the pools are
# outputs aliased to their inputs, and the new token's row is set in
# the page's VMEM copy (the page holding it is always a live one) before
# that page is scored, then carried back to the pool.  A single row is
# narrower than the pool's (8, 128) HBM tile, so the float kernel
# copies back the tile-aligned group of rows around it, and the q8
# kernel the whole page.  The pool then never leaves the layout it
# arrives in, and XLA needs no scatter, layout copy or slice around the
# kernels.


# K+V bytes a DMA group of whole pages should reach: copies large enough
# to run near HBM bandwidth, and few loop trips per lane
_GROUP_BYTES = 512 * 1024


def _pages_per_group(hkv: int, ps: int, d: int, itemsize: int,
                     t: int) -> int:
    """Smallest page count whose K+V bytes reach ``_GROUP_BYTES``,
    capped at the table width: one 512 KiB page of olmo-1b's bf16 pool
    (16 KV heads x 64 x 128), eight 64 KiB pages of qwen2.5-1.5b's (2 KV
    heads).  Double-buffered, olmo-1b's f32 pool takes the most VMEM of
    the served pools: 2 MiB."""
    page_bytes = 2 * hkv * ps * d * itemsize
    return max(1, min(t, -(-_GROUP_BYTES // page_bytes)))


def _fold_page(q, k, v, kv_len, j, ps: int, acc_ref, m_ref, l_ref, *,
               k_scale=None, v_scale=None):
    """Fold table slot ``j``, one page of every KV head, into the running
    softmax state of each query head: the paged form of
    :func:`_flash_block`.  q: (Hkv, G, D) float32, scaled; k, v:
    (Hkv, ps, D) float32; state (Hkv, G, D) and (Hkv, G, 1).  Per-token
    scales (Hkv, ps) of an int8 page multiply the scores and the softmax
    weights (``q.(s k) = s (q.k)``), so the page is never dequantized."""
    s = jnp.einsum("hgd,hsd->hgs", q, k, preferred_element_type=jnp.float32)
    if k_scale is not None:
        s = s * k_scale[:, None, :]
    k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = k_pos < kv_len
    s = jnp.where(mask, s, _NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
    m_ref[...] = m_new
    if v_scale is not None:
        p = p * v_scale[:, None, :]
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hgs,hsd->hgd", p, v, preferred_element_type=jnp.float32)


def _store_heads(o_ref, acc_ref, l_ref):
    """The paged form of :func:`_flash_store`: (Hkv, G, D) per lane."""
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)                      # all-dead lane
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _write_rows(ps: int, itemsize: int) -> int:
    """Rows around the new token that the float kernel copies back to
    the pool: one VMEM tile of the pool's dtype (8 float32, 16 bfloat16
    rows), or the whole page if it is shorter."""
    return min(ps, 8 * (4 // itemsize))


def _new_token_rows(k, v):
    """(B, Hkv, D) new key/value rows -> (B, Hkv, 1, D) float32 kernel
    operands (exact for every pool dtype)."""
    return (k.astype(jnp.float32)[:, :, None, :],
            v.astype(jnp.float32)[:, :, None, :])


def _paged_kernel(layer_ref, len_ref, pos_ref, bt_ref, q_ref, kn_ref,
                  vn_ref, k_in, v_in, o_ref, k_hbm, v_hbm, k_buf, v_buf,
                  sems, acc_ref, m_ref, l_ref, *, scale: float, ps: int,
                  pages_per_group: int, rows: int):
    del k_in, v_in                      # the same buffers as k_hbm, v_hbm
    b = pl.program_id(0)
    layer = layer_ref[0]
    kv_len = len_ref[b]
    n_live = jnp.minimum(pl.cdiv(kv_len, ps), bt_ref.shape[1])
    n_groups = pl.cdiv(n_live, pages_per_group)
    pos = pos_ref[b]
    w_slot = pos // ps                  # table slot of the new token
    w_row0 = pl.multiple_of(pos % ps // rows * rows, rows)
    w_page = bt_ref[b, w_slot]

    def page_copies(gi, slot, p):
        """The K and V copies of page ``p`` of group ``gi``: one whole
        (Hkv, ps, D) page of every KV head each, contiguous in the pool."""
        page = bt_ref[b, gi * pages_per_group + p]
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      k_buf.at[slot, p], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[slot, p], sems.at[1, slot]))

    def for_live_pages(gi, slot, fn):
        for p in range(pages_per_group):
            @pl.when(gi * pages_per_group + p < n_live)
            def _():
                for copy in page_copies(gi, slot, p):
                    fn(copy)

    def write_copies(slot, p):
        """The copies of the new token's row group, from page ``p`` of
        the buffer slot back to its page in the pool."""
        return tuple(
            pltpu.make_async_copy(
                buf.at[slot, p, :, pl.ds(w_row0, rows)],
                hbm.at[layer, w_page, :, pl.ds(w_row0, rows)],
                sems.at[2, i])
            for i, (buf, hbm) in enumerate(((k_buf, k_hbm),
                                            (v_buf, v_hbm))))

    _flash_init(acc_ref, m_ref, l_ref)
    q = q_ref[0].astype(jnp.float32) * scale             # (Hkv, G, D)
    for_live_pages(0, 0, lambda c: c.start())

    def group(gi, carry):
        slot = gi % 2
        w_here = w_slot // pages_per_group == gi
        w_p = w_slot % pages_per_group

        @pl.when(gi + 1 < n_groups)                      # double buffer
        def _prefetch():
            for_live_pages(gi + 1, 1 - slot, lambda c: c.start())

        for_live_pages(gi, slot, lambda c: c.wait())

        @pl.when(w_here)
        def _write():
            for buf, new_ref in ((k_buf, kn_ref), (v_buf, vn_ref)):
                view = buf.at[slot, w_p, :, pl.ds(w_row0, rows)]
                cur = view[...].astype(jnp.float32)      # (Hkv, rows, D)
                hit = jax.lax.broadcasted_iota(
                    jnp.int32, cur.shape, 1) == pos % ps - w_row0
                view[...] = jnp.where(hit, new_ref[0], cur).astype(
                    view.dtype)
            for copy in write_copies(slot, w_p):
                copy.start()

        def page(i, carry):
            j = gi * pages_per_group + i
            _fold_page(q, k_buf[slot, i].astype(jnp.float32),
                       v_buf[slot, i].astype(jnp.float32), kv_len, j, ps,
                       acc_ref, m_ref, l_ref)
            return carry

        n_here = jnp.minimum(pages_per_group, n_live - gi * pages_per_group)
        carry = jax.lax.fori_loop(0, n_here, page, carry)

        @pl.when(w_here)                 # before the slot is refilled
        def _written():
            for copy in write_copies(slot, w_p):
                copy.wait()

        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    _store_heads(o_ref, acc_ref, l_ref)


def decode_attention_paged_pallas(q, k, v, k_pages, v_pages, block_tables,
                                  kv_lengths, positions, layer, *,
                                  scale=None, interpret: bool = False):
    """Block-table decode attention over one layer of a stacked page
    pool, writing the step's new key and value into the pool first.

    q: (B, H, D); k/v: (B, Hkv, D) the new token's key and value;
    k_pages/v_pages: (L, P, Hkv, ps, D); block_tables: (B, T) int32
    physical page ids in logical order; kv_lengths: (B,) int32 keys to
    attend, the new one included; positions: (B,) int32 the new token's
    slot in the lane's table (``< kv_lengths``); layer: int32 scalar.
    Returns ``(out (B, H, D), k_pages, v_pages)``, the pools updated in
    place (aliased to the inputs).

    One grid step per lane.  The pools stay in HBM; the kernel walks the
    lane's ``ceil(len/ps)`` live table slots in groups of whole pages,
    double-buffered, each page one copy of all its KV heads.  The page
    holding the new token gets its row in VMEM before it is scored, and
    the tile-aligned rows around it go back to the pool
    (:func:`_write_rows`).  Every query head of a KV head is scored at
    once, as (Hkv, H/Hkv, ps) per page, into one online-softmax state
    per query head (float32 scores, max, sum and accumulator).  Pages
    per group: the fewest whose K and V bytes reach 512 KiB, at most the
    table width (:func:`_pages_per_group`).
    """
    b, h, d = q.shape
    _, _, hkv, ps, _ = k_pages.shape
    t = block_tables.shape[1]
    assert h % hkv == 0
    group = h // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    itemsize = k_pages.dtype.itemsize
    g = _pages_per_group(hkv, ps, d, itemsize, t)
    kernel = functools.partial(_paged_kernel, scale=scale, ps=ps,
                               pages_per_group=g,
                               rows=_write_rows(ps, itemsize))
    lane = pl.BlockSpec((1, hkv, group, d), lambda bb, *_: (bb, 0, 0, 0))
    new = pl.BlockSpec((1, hkv, 1, d), lambda bb, *_: (bb, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[lane, new, new, pool, pool],
        out_specs=[lane, pool, pool],
        scratch_shapes=[
            pltpu.VMEM((2, g, hkv, ps, d), k_pages.dtype),
            pltpu.VMEM((2, g, hkv, ps, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((3, 2)),
            pltpu.VMEM((hkv, group, d), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
        ],
    )
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      kv_lengths.astype(jnp.int32), positions.astype(jnp.int32),
      block_tables.astype(jnp.int32), q.reshape(b, hkv, group, d),
      *_new_token_rows(k, v), k_pages, v_pages)
    return out.reshape(b, h, d), k_pages, v_pages


def _paged_q8_kernel(layer_ref, len_ref, pos_ref, bt_ref, q_ref, kn_ref,
                     ksn_ref, vn_ref, vsn_ref, kq_ref, ks_ref, vq_ref,
                     vs_ref, o_ref, kq_out, ks_out, vq_out, vs_out, acc_ref,
                     m_ref, l_ref, *, scale: float, ps: int):
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    kv_len = len_ref[b]
    pos = pos_ref[b]
    here = j == pos // ps                # this slot holds the new token

    def tile(ref, new_ref):
        """The page of ``ref`` in VMEM as float32, with the new token's
        row (its lane, for a scale page) set."""
        cur = ref[0, 0].astype(jnp.float32)   # (Hkv, ps, D) or (Hkv, ps)
        hit = here & (jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
                      == pos % ps)
        return jnp.where(hit, new_ref[0], cur)

    @pl.when(j * ps < kv_len)                  # skip dead pages entirely
    def _page():
        kq, ks = tile(kq_ref, kn_ref), tile(ks_ref, ksn_ref)
        vq, vs = tile(vq_ref, vn_ref), tile(vs_ref, vsn_ref)

        @pl.when(here)
        def _write():
            for out, val in ((kq_out, kq), (ks_out, ks), (vq_out, vq),
                             (vs_out, vs)):
                out[0, 0] = val.astype(out.dtype)

        q = q_ref[0].astype(jnp.float32) * scale         # (Hkv, G, D)
        _fold_page(q, kq, vq, kv_len, j, ps, acc_ref, m_ref, l_ref,
                   k_scale=ks, v_scale=vs)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        _store_heads(o_ref, acc_ref, l_ref)


def decode_attention_paged_q8_pallas(q, k, k_scale, v, v_scale, k_pages,
                                     k_scale_pages, v_pages, v_scale_pages,
                                     block_tables, kv_lengths, positions,
                                     layer, *, scale=None,
                                     interpret: bool = False):
    """Paged int8-KV decode over one layer of stacked pools, writing the
    step's quantized key and value first; the same contract as
    :func:`decode_attention_paged_pallas`.

    k/v: (B, Hkv, D) int8 new rows, k_scale/v_scale: (B, Hkv) float32
    their per-(token, head) scales; k_pages/v_pages: (L, P, Hkv, ps, D)
    int8; scale pools (L, P, Hkv, ps) float32.  Returns ``(out,
    k_pages, k_scale_pages, v_pages, v_scale_pages)``.

    Grid (lane, table slot): each step fetches one whole page of every
    KV head, values and scales, through the table, its index clamped to
    the lane's last live page so pages past the live length are never
    fetched.  Scales apply to the scores and to the softmax weights
    (``q.(s k) = s (q.k)``), so no tile is dequantized.  The page
    holding the new token is written back whole: its output block is
    pinned to that page for the lane's whole row of steps.
    """
    b, h, d = q.shape
    _, _, hkv, ps, _ = k_pages.shape
    t = block_tables.shape[1]
    group = h // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    kernel = functools.partial(_paged_q8_kernel, scale=scale, ps=ps)

    def page_index(bb, j, layer_ref, lens_ref, pos_ref, bt_ref):
        jj = jnp.minimum(j, _last_live_block(lens_ref, bb, ps))
        return (layer_ref[0], bt_ref[bb, jj], 0, 0, 0)

    def new_page_index(bb, j, layer_ref, lens_ref, pos_ref, bt_ref):
        return (layer_ref[0], bt_ref[bb, pos_ref[bb] // ps], 0, 0, 0)

    def lane_index(bb, *_):
        return (bb, 0, 0, 0)

    vals = (1, 1, hkv, ps, d)
    scales = (1, 1, hkv, ps)
    pool_specs = [pl.BlockSpec(vals, page_index),
                  pl.BlockSpec(scales, lambda *a: page_index(*a)[:4]),
                  pl.BlockSpec(vals, page_index),
                  pl.BlockSpec(scales, lambda *a: page_index(*a)[:4])]
    out_specs = [pl.BlockSpec(vals, new_page_index),
                 pl.BlockSpec(scales, lambda *a: new_page_index(*a)[:4]),
                 pl.BlockSpec(vals, new_page_index),
                 pl.BlockSpec(scales, lambda *a: new_page_index(*a)[:4])]
    lane = pl.BlockSpec((1, hkv, group, d), lane_index)
    new_vals = pl.BlockSpec((1, hkv, 1, d), lane_index)
    new_scales = pl.BlockSpec((1, hkv, 1), lambda bb, *_: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, t),
        in_specs=[lane, new_vals, new_scales, new_vals, new_scales,
                  *pool_specs],
        out_specs=[lane, *out_specs],
        scratch_shapes=[
            pltpu.VMEM((hkv, group, d), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
        ],
    )
    pools = (k_pages, k_scale_pages, v_pages, v_scale_pages)
    kn, vn = _new_token_rows(k, v)
    out, *pools = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype)]
        + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools],
        input_output_aliases={9: 1, 10: 2, 11: 3, 12: 4},
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      kv_lengths.astype(jnp.int32), positions.astype(jnp.int32),
      block_tables.astype(jnp.int32), q.reshape(b, hkv, group, d),
      kn, k_scale.astype(jnp.float32)[:, :, None], vn,
      v_scale.astype(jnp.float32)[:, :, None], *pools)
    return (out.reshape(b, h, d), *pools)


# ----------------------------------------------------------------------
# quantized-KV variant (q8_0 along the key axis)
# ----------------------------------------------------------------------

def _decode_q8_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, len_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, scale: float, bk: int,
                      qblock: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = _dequant_tile(kq_ref, ks_ref, qblock)
    v = _dequant_tile(vq_ref, vs_ref, qblock)
    _flash_block(q, k, v, len_ref[0], j, bk, acc_ref, m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        _flash_store(o_ref, acc_ref, l_ref)


def decode_attention_q8_pallas(q, k_q, k_scale, v_q, v_scale, kv_lengths, *,
                               scale=None, bk: int = 512, qblock: int = 32,
                               interpret: bool = False):
    """Quantized-KV decode.

    k_q/v_q: (B, Hkv, S, D) int8; k_scale/v_scale: (B, Hkv, S/qblock, 1)
    f32 per-32-key-block scales (per head, shared across D).
    """
    b, h, d = q.shape
    _, hkv, sk, _ = k_q.shape
    group = h // hkv
    bk = min(bk, sk)
    assert sk % bk == 0 and bk % qblock == 0
    scale = float(scale if scale is not None else d ** -0.5)
    srows = bk // qblock
    kernel = functools.partial(_decode_q8_kernel, scale=scale, bk=bk,
                               qblock=qblock)
    q4 = q[:, :, None, :]
    return pl.pallas_call(
        kernel,
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bb, hh, j: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, j: (bb, hh // group, j, 0)),
            pl.BlockSpec((1, 1, srows, 1),
                         lambda bb, hh, j: (bb, hh // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, hh, j: (bb, hh // group, j, 0)),
            pl.BlockSpec((1, 1, srows, 1),
                         lambda bb, hh, j: (bb, hh // group, j, 0)),
            pl.BlockSpec((1,), lambda bb, hh, j: (bb,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda bb, hh, j: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q4, k_q, k_scale, v_q, v_scale, kv_lengths)[:, :, 0, :]


def _decode_q8_la_kernel(len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                         bk: int, qblock: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    kv_len = len_ref[pl.program_id(0)]

    @pl.when(j * bk < kv_len)                  # skip dead blocks entirely
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = _dequant_tile(kq_ref, ks_ref, qblock)
        v = _dequant_tile(vq_ref, vs_ref, qblock)
        _flash_block(q, k, v, kv_len, j, bk, acc_ref, m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        _flash_store(o_ref, acc_ref, l_ref)


def decode_attention_q8_lengthaware_pallas(q, k_q, k_scale, v_q, v_scale,
                                           kv_lengths, *, scale=None,
                                           bk: int = 512, qblock: int = 32,
                                           interpret: bool = False):
    """Length-aware quantized-KV decode: q8 tiles (values AND scales)
    past the live length are never fetched."""
    b, h, d = q.shape
    _, hkv, sk, _ = k_q.shape
    group = h // hkv
    bk = min(bk, sk)
    assert sk % bk == 0 and bk % qblock == 0
    scale = float(scale if scale is not None else d ** -0.5)
    srows = bk // qblock
    kernel = functools.partial(_decode_q8_la_kernel, scale=scale, bk=bk,
                               qblock=qblock)
    q4 = q[:, :, None, :]

    def kv_index(bb, hh, j, lens_ref):
        jj = jnp.minimum(j, _last_live_block(lens_ref, bb, bk))
        return (bb, hh // group, jj, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d),
                         lambda bb, hh, j, lens_ref: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, srows, 1), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, srows, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bb, hh, j, lens_ref: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(kv_lengths.astype(jnp.int32), q4, k_q, k_scale, v_q,
      v_scale)[:, :, 0, :]
