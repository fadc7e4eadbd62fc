"""Pure-jnp oracle for the paged-attention kernel.

The same math ``models/attention.paged_attention`` runs on the 'ref'
backend, as a standalone function over raw pools — gather the whole page
table into a dense context, mask by absolute position, softmax. Used by
the kernel parity tests and the kernel bench; deliberately materializes
the ``(B, P*page_size, kv, hd)`` context the kernel exists to avoid.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_pages(pool, page_table):
    """(n_pages, KV, ps, hd) pool, (B, P) table -> the (B, P*ps, KV, hd)
    gathered context of every slot, in position order."""
    b, p_log = page_table.shape
    _, kv, ps, hd = pool.shape
    return pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        b, p_log * ps, kv, hd)


def paged_attention_ref(q, k_pool, v_pool, page_table, positions, *,
                        window: Optional[int] = None):
    """q: (B, C, H, hd); k/v_pool: (n_pages, KV, ps, hd) head-major pools;
    page_table: (B, P) int32; positions: (B, C) int32 absolute positions.

    Returns (B, C, H, hd) f32. Rows whose query is invalid (an inactive
    slot / past-``n_tokens`` tail) return finite garbage, same as the
    kernel path — callers discard them downstream.
    """
    b, c, h, hd = q.shape
    kv, ps = k_pool.shape[1], k_pool.shape[2]
    p_log = page_table.shape[1]
    g = h // kv
    k_ctx = gather_pages(k_pool, page_table)
    v_ctx = gather_pages(v_pool, page_table)

    qg = q.reshape(b, c, kv, g, hd)
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg, k_ctx,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    k_pos = jnp.arange(p_log * ps, dtype=jnp.int32)
    mask = k_pos[None, None, :] <= positions[:, :, None]        # (B, C, K)
    if window is not None:
        mask &= (positions[:, :, None] - k_pos[None, None, :]) < window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)

    pattn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bckh->bkgqh", pattn, v_ctx.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd)
