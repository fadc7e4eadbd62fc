"""Jit'd wrapper: model-layout paged attention with the flash-decode
cross-split combine.

``paged_flash_attention`` is the serving engine's pallas-backend attention
(``models/attention.paged_attention`` dispatches here when the resolved
backend is 'pallas'): the kernel walks each slot's page table a block of
pages at a time, every KV head per step, up to the slot's own length (the
gathered ``(B, P*page_size, ...)`` context is never materialized) and
emits per-KV-split UNNORMALIZED partials (acc, m, l); this wrapper runs
the flash-decode combine

    m*   = max_s m_s
    out  = sum_s exp(m_s - m*) * acc_s  /  max(sum_s exp(m_s - m*) * l_s, eps)

which is exact — for ``kv_splits == 1`` it reduces to the ordinary
``acc / l`` normalization, so 1-split and N-split agree to float rounding
(tested). Empty split lanes (every page skipped) carry (m=-inf, l=0,
acc=0) and drop out of both sums.

Interpret mode resolves through ``kernels.use_interpret()`` (compiled on
TPU, interpret elsewhere, ``REPRO_PALLAS_INTERPRET`` overrides).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.paged_attention import ref as ref_lib
from repro.kernels.paged_attention.paged_attention import paged_flash_fwd


@functools.partial(jax.jit,
                   static_argnames=("window", "kv_splits", "interpret"))
def _paged_flash_attention(q, k_pool, v_pool, page_table, positions, *,
                           window=None, kv_splits: int = 1, interpret=None):
    b, c, h, hd = q.shape
    kv = k_pool.shape[1]
    g = h // kv
    qg = q.reshape(b, c, kv, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, kv, c * g, hd)
    start = positions[:, 0]
    if interpret is None:
        interpret = use_interpret()
    acc, m, l = paged_flash_fwd(
        qg.astype(jnp.float32), k_pool, v_pool, page_table, start, g=g,
        window=window, kv_splits=kv_splits, interpret=interpret)
    # cross-split softmax combine (exact; identity at kv_splits == 1)
    m_star = jnp.max(m, axis=2)                            # (B, KV, C*g, 1)
    w = jnp.exp(m - m_star[:, :, None])                    # (B, KV, S, C*g, 1)
    l_tot = jnp.sum(w * l, axis=2)
    acc_tot = jnp.sum(w * acc, axis=2)
    out = acc_tot / jnp.maximum(l_tot, 1e-30)              # (B, KV, C*g, hd)
    return out.reshape(b, kv, c, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, hd)


def paged_flash_attention(q, k_pool, v_pool, page_table, positions, *,
                          window=None, kv_splits: int = 1, interpret=None):
    """q: (B, C, H, hd); k/v_pool: (n_pages, KV, ps, hd) head-major pools;
    page_table: (B, P) int32; positions: (B, C) int32 ABSOLUTE positions —
    the engine contract ``positions = start_pos[:, None] + arange(C)``
    (the kernel's page-skip predicates assume row 0 is the tick start).

    Returns (B, C, H, hd) f32 attention output; invalid query rows carry
    finite garbage exactly like the ref path.
    """
    return _paged_flash_attention(q, k_pool, v_pool, page_table, positions,
                                  window=window, kv_splits=kv_splits,
                                  interpret=interpret)


paged_attention_ref = ref_lib.paged_attention_ref
gather_pages = ref_lib.gather_pages
