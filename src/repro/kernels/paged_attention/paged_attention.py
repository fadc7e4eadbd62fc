"""Pallas TPU kernel: page-table gather fused with flash-decode attention.

The serving engine's mixed step (``models/attention.paged_attention``) is
the hot path of continuous batching, and its jnp reference gathers the
ENTIRE paged KV pool into a dense ``(B, P*page_size, kv, hd)`` context and
materializes a full score tensor every tick — O(max-context) HBM traffic
and FLOPs per decode token. This kernel is the EIE-style fix: the page
table rides into SMEM as a scalar-prefetch operand and each grid step DMAs
exactly ONE physical KV page into VMEM via the BlockSpec index map — the
gathered context never exists. Attention over the pages is the standard
online-softmax recurrence (running max / sum / accumulator in VMEM), with

* causal-by-absolute-position masking: query at absolute position q sees
  keys at absolute positions <= q (the mixed prefill/decode contract),
* optional sliding-window masking ((q_pos - k_pos) < window),
* page skipping: pages entirely above the causal frontier or entirely
  below the window floor are skipped with ``@pl.when`` (FLOPs saved on
  hardware; the trip count stays static so the Mosaic schedule does too),
* a flash-decode KV-split axis: the logical pages of a slot are cut into
  ``kv_splits`` segments processed by independent grid lanes, each
  emitting an UNNORMALIZED partial (acc, m, l); the cross-split softmax
  combine lives in ``ops.paged_flash_attention``. Decode ticks (one query
  row) have no query-axis parallelism to offer — splitting the KV axis is
  what keeps long-context decode from serializing on one core.

Grid: (B, KV, S, PP) with the page axis innermost ('arbitrary'); B, kv
head and split lanes are 'parallel'. Queries arrive pre-grouped as
(B, KV, C*g, hd) — the g query heads sharing a kv head are flattened into
the row axis (row r is token r // g) of one (C*g, hd) x (hd, page_size)
matmul per page. Pools are head-major, (n_pages, KV, page_size, hd), so one
grid step's K/V tile is a whole (page_size, hd) slab: every block's last
two dims are whole array dims, which Mosaic's tiling rule (multiples of
(8, 128) or whole dims) accepts at any page size, head width and head
count. Query positions are not an operand: the engine contract is
``positions = start + arange(C)``, so the kernel derives them from the
scalar-prefetched ``start``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NAME = "paged_flash_attention"   # kernel_name in HLO and profiler traces


def _kernel(table_ref, start_ref,            # scalar-prefetch (SMEM)
            q_ref, k_ref, v_ref,             # VMEM tiles
            acc_out, m_out, l_out,           # unnormalized partials
            m_sc, l_sc, acc_sc,              # VMEM carries across pages
            *, scale, window, ps, g, n_pages_per_split, n_logical_pages):
    b = pl.program_id(0)
    s = pl.program_id(2)
    j = pl.program_id(3)
    rows = q_ref.shape[2]                    # C * g
    c = rows // g

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    page = s * n_pages_per_split + j
    start = start_ref[b]
    # page skip: logical page `page` covers absolute positions
    # [page*ps, page*ps + ps). Past the table, above the causal frontier
    # (first key position > last query position) or entirely below the
    # sliding-window floor -> contributes nothing, skip the matmuls.
    run = page < n_logical_pages
    run &= page * ps <= start + c - 1
    if window is not None:
        run &= page * ps + ps - 1 >= start - window + 1

    @pl.when(run)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)                # (C*g, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0)
        q_pos = start + (row // g if g > 1 else row)       # absolute q pos
        k_pos = page * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        mask = q_pos >= k_pos                              # causal
        if window is not None:
            mask &= (q_pos - k_pos) < window
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_sc[...]                                 # (C*g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = corr * acc_sc[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_pages_per_split - 1)
    def _emit():
        # UNNORMALIZED partial per split lane: ops.paged_flash_attention
        # runs the cross-split combine. A lane whose pages were all skipped
        # emits (m=-inf, l=0, acc=0) and drops out of the combine.
        m_out[0, 0, 0] = m_sc[...]
        l_out[0, 0, 0] = l_sc[...]
        acc_out[0, 0, 0] = acc_sc[...]


def paged_flash_fwd(q, k_pool, v_pool, page_table, start, *, g: int,
                    window=None, kv_splits: int = 1,
                    interpret: bool = False):
    """Unnormalized flash-decode partials over a block-paged KV pool.

    q          : (B, KV, C*g, hd) queries grouped per kv head (row r is
                 token r // g of the tick)
    k/v_pool   : (n_pages, KV, page_size, hd) head-major page pools
    page_table : (B, P) int32 — physical page of each slot's logical page
    start      : (B,) int32 first absolute position of the tick (token t
                 sits at start + t)

    Returns (acc, m, l): acc (B, KV, S, C*g, hd) f32 and m/l
    (B, KV, S, C*g, 1) f32 — per-KV-split running max / sum / accumulator,
    to be combined by the caller (S = kv_splits).
    """
    b, kv, rows, hd = q.shape
    ps = k_pool.shape[2]
    n_logical = page_table.shape[1]
    s_lanes = int(kv_splits)
    assert 1 <= s_lanes <= n_logical, (kv_splits, n_logical)
    assert rows % g == 0, (rows, g)
    pp = -(-n_logical // s_lanes)            # pages per split lane (ceil)
    scale = hd ** -0.5

    def q_map(bi, ki, si, ji, table_s, start_s):
        return (bi, ki, 0, 0)

    def kv_map(bi, ki, si, ji, table_s, start_s):
        # THE gather: the page axis of the pool is indexed through the
        # SMEM-prefetched page table, so only this slot's current page is
        # DMA'd. Lanes past the table end (si*pp + ji >= P) clamp to a
        # valid entry; the kernel's `run` predicate ignores their tile.
        page = jnp.minimum(si * pp + ji, n_logical - 1)
        return (table_s[bi, page], ki, 0, 0)

    def out_map(bi, ki, si, ji, table_s, start_s):
        return (bi, ki, si, 0, 0)

    kern = functools.partial(_kernel, scale=scale, window=window, ps=ps,
                             g=g, n_pages_per_split=pp,
                             n_logical_pages=n_logical)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, s_lanes, pp),
            in_specs=[
                pl.BlockSpec((1, 1, rows, hd), q_map),
                pl.BlockSpec((1, 1, ps, hd), kv_map),
                pl.BlockSpec((1, 1, ps, hd), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, rows, hd), out_map),
                pl.BlockSpec((1, 1, 1, rows, 1), out_map),
                pl.BlockSpec((1, 1, 1, rows, 1), out_map),
            ],
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, hd), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, s_lanes, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, s_lanes, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, s_lanes, rows, 1), jnp.float32),
        ],
        name=NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      q, k_pool, v_pool)
