"""Pure-jnp oracles for decode attention (dense + q8 KV)."""

from __future__ import annotations

import jax.numpy as jnp


def decode_attention_ref(q, k, v, kv_lengths, *, scale=None):
    """q: (B, H, D); k/v: (B, Hkv, S, D); kv_lengths: (B,).

    GQA is computed grouped (q reshaped to (B, Hkv, G, D)) -- no KV head
    replication is materialized, which both saves memory and keeps GSPMD
    shardings aligned to the Hkv axis for every head count (40H, 25H...).
    """
    b, h, d = q.shape
    _, hkv, sk, _ = k.shape
    group = h // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k.astype(jnp.float32))
    mask = jnp.arange(sk)[None, None, None, :] < \
        kv_lengths[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    denom = jnp.where(denom == 0.0, 1.0, denom)
    out = jnp.einsum("bkgs,bksd->bkgd", p / denom, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def dequant_kv_q8(k_q, k_scale, qblock: int = 32):
    """(B, Hkv, S, D) int8 + (B, Hkv, S/qblock, 1) f32 -> f32 KV."""
    scales = jnp.repeat(k_scale, qblock, axis=2)
    return k_q.astype(jnp.float32) * scales


def quantize_kv_q8(k, qblock: int = 32):
    """Per-(head, 32-key-block) symmetric int8 KV quantization."""
    b, hkv, s, d = k.shape
    kb = k.astype(jnp.float32).reshape(b, hkv, s // qblock, qblock, d)
    amax = jnp.max(jnp.abs(kb), axis=(3, 4), keepdims=True)
    scale = (amax / 127.0).reshape(b, hkv, s // qblock, 1)
    scale = jnp.where(scale == 0, 1.0, scale)
    kq = jnp.clip(jnp.round(
        kb / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return kq.reshape(b, hkv, s, d), scale


def decode_attention_q8_ref(q, k_q, k_scale, v_q, v_scale, kv_lengths, *,
                            scale=None, qblock: int = 32):
    k = dequant_kv_q8(k_q, k_scale, qblock)
    v = dequant_kv_q8(v_q, v_scale, qblock)
    return decode_attention_ref(q, k, v, kv_lengths, scale=scale)


# ----------------------------------------------------------------------
# paged (block-table) oracles
# ----------------------------------------------------------------------

def gather_pages(pages: jnp.ndarray, block_tables: jnp.ndarray,
                 layer) -> jnp.ndarray:
    """Materialize each lane's logical KV view from one layer of the
    stacked page pool.

    pages: (L, P, Hkv, ps, ...) -- values (..., D) or per-token scales
    (no trailing axis); block_tables: (B, T) int32 physical page ids in
    logical order -> (B, Hkv, T*ps, ...).  The layer is indexed together
    with the pages, so only the lanes' pages of that layer are read.
    Because the table lists the lane's pages in logical order, the
    gathered array holds exactly the values a dense per-lane cache
    would -- the paged-vs-dense parity tests lean on this being an
    identity up to page naming.
    """
    g = pages[layer, block_tables]             # (B, T, Hkv, ps, ...)
    b, t, hkv, ps = g.shape[:4]
    return jnp.swapaxes(g, 1, 2).reshape((b, hkv, t * ps) + g.shape[4:])


def write_token(pages: jnp.ndarray, new: jnp.ndarray,
                block_tables: jnp.ndarray, positions: jnp.ndarray,
                layer) -> jnp.ndarray:
    """Set each lane's new row (B, Hkv, ...) at its table position in
    one layer of the stacked pool (L, P, Hkv, ps, ...).  Distinct lanes
    own distinct pages (the allocator's invariant), so the rows never
    collide."""
    ps = pages.shape[3]
    page = jnp.take_along_axis(block_tables, (positions // ps)[:, None],
                               axis=1)[:, 0]
    return pages.at[layer, page, :, positions % ps].set(
        new.astype(pages.dtype))


def decode_attention_paged_ref(q, k, v, k_pages, v_pages, block_tables,
                               kv_lengths, positions, layer, *,
                               scale=None):
    """q: (B, H, D); new rows k/v (B, Hkv, D); pools (L, P, Hkv, ps, D);
    block_tables (B, T) -> (out, k_pages, v_pages)."""
    k_pages = write_token(k_pages, k, block_tables, positions, layer)
    v_pages = write_token(v_pages, v, block_tables, positions, layer)
    out = decode_attention_ref(q, gather_pages(k_pages, block_tables, layer),
                               gather_pages(v_pages, block_tables, layer),
                               kv_lengths, scale=scale)
    return out, k_pages, v_pages


def decode_attention_paged_q8_ref(q, k, k_scale, v, v_scale, k_pages,
                                  k_scale_pages, v_pages, v_scale_pages,
                                  block_tables, kv_lengths, positions,
                                  layer, *, scale=None):
    """Paged int8 oracle: int8 pools (L, P, Hkv, ps, D) with per-(token,
    head) float32 scale pools (L, P, Hkv, ps); new rows k/v (B, Hkv, D)
    int8 and scales (B, Hkv).  Returns (out, k_pages, k_scale_pages,
    v_pages, v_scale_pages)."""
    pools = tuple(
        write_token(pool, new, block_tables, positions, layer)
        for pool, new in ((k_pages, k), (k_scale_pages, k_scale),
                          (v_pages, v), (v_scale_pages, v_scale)))

    def dequant(vals, scales):
        return (gather_pages(vals, block_tables, layer).astype(jnp.float32)
                * gather_pages(scales, block_tables, layer)[..., None])

    out = decode_attention_ref(q, dequant(pools[0], pools[1]),
                               dequant(pools[2], pools[3]), kv_lengths,
                               scale=scale)
    return (out, *pools)
