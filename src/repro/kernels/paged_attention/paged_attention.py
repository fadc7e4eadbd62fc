"""Pallas TPU kernel: page-table gather fused with flash-decode attention.

The serving engine's mixed step (``models/attention.paged_attention``) is
the hot path of continuous batching, and its jnp reference gathers the
ENTIRE paged KV pool into a dense ``(B, P*page_size, kv, hd)`` context and
materializes a full score tensor every tick — O(max-context) HBM traffic
and FLOPs per decode token. This kernel walks each slot's page table up to
the slot's own length instead; the gathered context never exists.
Attention over the pages is the standard online-softmax recurrence (running
max / sum / accumulator in VMEM, all f32), with

* causal-by-absolute-position masking: query at absolute position q sees
  keys at absolute positions <= q (the mixed prefill/decode contract),
* optional sliding-window masking ((q_pos - k_pos) < window),
* a flash-decode KV-split axis: a slot's blocks of pages are cut into
  ``kv_splits`` segments processed by independent grid lanes, each
  emitting an UNNORMALIZED partial (acc, m, l); the cross-split softmax
  combine lives in ``ops.paged_flash_attention``.

Grid: (B, S, NB). One grid step handles a BLOCK of ``pages_per_block``
consecutive logical pages of one slot for EVERY kv head (a static loop over
heads inside the step), not one page of one head: the fixed cost of a grid
step, not HBM, set the pace of a page-per-step walk. B and the split lanes
are 'parallel', the block axis innermost ('arbitrary'). Queries arrive
pre-grouped as (B, KV, C*g, hd) — the g query heads sharing a kv head are
flattened into the row axis (row r is token r // g) — and the m / l / acc
carries hold every head, (KV, C*g, ·).

DMA scheme: the pools stay in HBM (``memory_space=pl.ANY``). Pools are
head-major, (n_pages, KV, page_size, hd), so one physical page of every
head is one contiguous (KV, page_size, hd) slab. Each step starts one
``make_async_copy`` per page of a block, looked up in the SMEM-prefetched
page table, into a double-buffered VMEM scratch
(2, pages_per_block, KV, page_size, hd) per pool: block i + 1's copies
start before block i's are waited on, so they fly while block i is
computed, and a lane's first live block starts its own. Mosaic slices an
HBM pool only in whole 128-lane tiles, so where hd is not a multiple of 128
(hd 64: smollm-360m) the kernel takes each pool once per page of a block, each operand a
(1, KV, page_size, hd) BlockSpec whose index map does the same lookup, and
Pallas double-buffers them; the step body is the same. (At qwen3-0.6b's
shapes the manual copies take 0.60 ms a decode call, the BlockSpecs 1.05
ms, on one v5e.)

The walk stops at each slot's own frontier: a block entirely above the
causal frontier (first key past the tick's last query position), entirely
below the sliding-window floor, or past the table starts no copy and does
no compute, and the next block is fetched only if it will run (the
BlockSpec index maps clamp to the lane's nearest live block, so their block
indices repeat and Pallas copies nothing). Skipped steps cost only the bare
grid step. The causal mask drops the keys of a live block past the
frontier, and those of a last block that overhangs the table width P (it
re-reads the table's last page): no valid query sits at or past
P * page_size.

``pages_per_block`` is derived from static shapes (``pages_per_block``
below): as many pages as keep one step's K+V near ``KV_STEP_BYTES`` (1 MiB,
so a step's HBM time is several times the fixed cost of a grid step; 16
pages at qwen3-0.6b's KV 8 x hd 128, page 16, bf16) and one head's f32
score tile under ``SCORE_TILE_BYTES``, never more than a split lane's share
of the table. ``vmem_limit_bytes`` is raised only when the step's estimated
VMEM passes the default scoped limit: the 512-row prefill chunk's does
(28 MiB at qwen3-0.6b). Compiled alone it fits the default, but inside the
engine's whole step, left at the default, it ran out of VMEM on a v5e.
Query positions are not an operand: the engine contract is
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

KV_STEP_BYTES = 1 << 20          # K+V bytes one grid step aims to move
SCORE_TILE_BYTES = 1 << 21       # cap on one head's (C*g, block) f32 scores
DEFAULT_SCOPED_VMEM = 16 << 20   # Mosaic's default scoped VMEM limit (v5e)
MAX_VMEM = 100 << 20             # under v5e's 128 MiB of VMEM


def _page_bytes(kv, ps, hd, itemsize):
    """K+V of one page of every head, lanes padded to 128 as stored."""
    return 2 * kv * ps * (-(-hd // 128) * 128) * itemsize


def pages_per_block(rows: int, kv: int, ps: int, hd: int, itemsize: int,
                    n_logical: int, kv_splits: int = 1) -> int:
    """Pages of one slot per grid step, from the call's static shapes:
    enough for ~``KV_STEP_BYTES`` of K+V (every head), at most a
    ``SCORE_TILE_BYTES`` f32 score tile per head, at most a split lane's
    share of the ``n_logical``-page table."""
    ppb = max(1, KV_STEP_BYTES // _page_bytes(kv, ps, hd, itemsize))
    ppb = min(ppb, max(1, SCORE_TILE_BYTES // (rows * ps * 4)))
    return min(ppb, -(-n_logical // kv_splits))


def _vmem_bytes(rows, kv, ps, hd, itemsize, ppb):
    """Rough VMEM of one step, tiles padded to (8, 128): the double-buffered
    K/V block, the double-buffered q / acc / m / l blocks and the carries,
    and one head's f32 scores and probabilities and upcast K and V."""
    r8, lanes, bk = -(-rows // 8) * 8, -(-hd // 128) * 128, ppb * ps
    kv_block = ppb * _page_bytes(kv, ps, hd, itemsize)
    rows_f32 = kv * r8 * (2 * lanes + 2 * 128) * 4      # q or acc, m, l
    temps = (4 * r8 * -(-bk // 128) * 128 + 2 * bk * lanes) * 4
    return 2 * kv_block + 3 * rows_f32 + temps


def _live_range(start, c, *, bk, window, n_blocks, blocks_per_split, s):
    """First and last live block of split lane ``s``: block i covers
    absolute positions [i*bk, i*bk + bk); it is live unless it lies past
    the table, above the causal frontier (first key position > last query
    position) or entirely below the sliding-window floor."""
    lo = s * blocks_per_split
    hi = jnp.minimum(lo + blocks_per_split, n_blocks) - 1
    hi = jnp.minimum(hi, (start + c - 1) // bk)
    if window is not None:
        lo = jnp.maximum(lo, jnp.maximum(start - window + 1, 0) // bk)
    return lo, hi


def _kernel(table_ref, start_ref,            # scalar-prefetch (SMEM)
            q_ref, *refs,                    # q block, pools or pages, ...
            scale, window, ps, g, ppb, n_blocks, blocks_per_split,
            n_logical, manual):
    if manual:
        k_hbm, v_hbm, acc_out, m_out, l_out, k_buf, v_buf, sem = refs[:8]
    else:
        k_pages, v_pages = refs[:ppb], refs[ppb:2 * ppb]
        acc_out, m_out, l_out = refs[2 * ppb:2 * ppb + 3]
    m_sc, l_sc, acc_sc = refs[-3:]           # VMEM carries across blocks
    b = pl.program_id(0)
    s = pl.program_id(1)
    j = pl.program_id(2)
    kv, rows, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    c = rows // g
    bk = ppb * ps
    start = start_ref[b]
    blk = s * blocks_per_split + j
    lo, hi = _live_range(start, c, bk=bk, window=window, n_blocks=n_blocks,
                         blocks_per_split=blocks_per_split, s=s)
    slot = j % 2

    def copies(i, buf_slot):
        # one (KV, ps, hd) slab per page; a block overhanging the table
        # re-reads the table's last page
        out = []
        for p in range(ppb):
            page = table_ref[b, jnp.minimum(i * ppb + p, n_logical - 1)]
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                out.append(pltpu.make_async_copy(
                    pool.at[page], buf.at[buf_slot, p], sem.at[buf_slot]))
        return out

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    if manual:
        @pl.when((blk == lo) & (blk <= hi))  # the lane's first live block
        def _first_fetch():
            for cp in copies(blk, slot):
                cp.start()

    @pl.when((blk >= lo) & (blk <= hi))
    def _block():
        if manual:
            @pl.when(blk + 1 <= hi)          # next block, other buffer
            def _prefetch():
                for cp in copies(blk + 1, 1 - slot):
                    cp.start()

            for cp in copies(blk, slot):
                cp.wait()

            def kv_of(h):
                return (k_buf[slot, :, h].reshape(bk, hd),
                        v_buf[slot, :, h].reshape(bk, hd))
        else:
            def kv_of(h):
                return (jnp.concatenate([r[0, h] for r in k_pages], axis=0),
                        jnp.concatenate([r[0, h] for r in v_pages], axis=0))

        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        q_pos = start + (row // g if g > 1 else row)       # absolute q pos
        k_pos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        mask = q_pos >= k_pos                              # causal
        if window is not None:
            mask &= (q_pos - k_pos) < window
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)            # (C*g, hd)
            k, v = kv_of(h)                                # (bk, hd) each
            sc = jax.lax.dot_general(q, k.astype(jnp.float32),
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sc = jnp.where(mask, sc * scale, NEG_INF)

            m_prev = m_sc[h]                               # (C*g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_sc[h] = corr * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = corr * acc_sc[h] + jax.lax.dot(
                p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(j == blocks_per_split - 1)
    def _emit():
        # UNNORMALIZED partial per split lane: ops.paged_flash_attention
        # runs the cross-split combine. A lane whose blocks were all skipped
        # emits (m=-inf, l=0, acc=0) and drops out of the combine.
        m_out[0, :, 0] = m_sc[...]
        l_out[0, :, 0] = l_sc[...]
        acc_out[0, :, 0] = acc_sc[...]


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
    itemsize = k_pool.dtype.itemsize
    n_logical = page_table.shape[1]
    s_lanes = int(kv_splits)
    assert 1 <= s_lanes <= n_logical, (kv_splits, n_logical)
    assert rows % g == 0, (rows, g)
    ppb = pages_per_block(rows, kv, ps, hd, itemsize, n_logical, s_lanes)
    n_blocks = -(-n_logical // ppb)
    nbl = -(-n_blocks // s_lanes)            # blocks per split lane (ceil)
    manual = hd % 128 == 0                   # else Mosaic refuses .at[page]
    live = functools.partial(_live_range, c=rows // g, bk=ppb * ps,
                             window=window, n_blocks=n_blocks,
                             blocks_per_split=nbl)

    def q_map(bi, si, ji, table_s, start_s):
        return (bi, 0, 0, 0)

    def page_map(p, bi, si, ji, table_s, start_s):
        # page p of the step's block, looked up in the SMEM-prefetched
        # table. Steps outside the lane's live range clamp to its nearest
        # live block, so their block index repeats and Pallas copies
        # nothing for them.
        lo, hi = live(start_s[bi], s=si)
        blk = jnp.minimum(jnp.maximum(si * nbl + ji, lo), hi)
        return (table_s[bi, jnp.minimum(blk * ppb + p, n_logical - 1)],
                0, 0, 0)

    def out_map(bi, si, ji, table_s, start_s):
        return (bi, 0, si, 0, 0)

    if manual:
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        block_buf = pltpu.VMEM((2, ppb, kv, ps, hd), k_pool.dtype)
        dma_scratch = [block_buf, block_buf, pltpu.SemaphoreType.DMA((2,))]
        pools = (k_pool, v_pool)
    else:
        pool_specs = 2 * [pl.BlockSpec((1, kv, ps, hd),
                                       functools.partial(page_map, p))
                          for p in range(ppb)]
        dma_scratch = []
        pools = (k_pool,) * ppb + (v_pool,) * ppb
    kern = functools.partial(_kernel, scale=hd ** -0.5, window=window, ps=ps,
                             g=g, ppb=ppb, n_blocks=n_blocks,
                             blocks_per_split=nbl, n_logical=n_logical,
                             manual=manual)
    vmem = _vmem_bytes(rows, kv, ps, hd, itemsize, ppb)
    limit = (None if vmem <= DEFAULT_SCOPED_VMEM
             else min(MAX_VMEM, vmem + vmem // 2))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s_lanes, nbl),
            in_specs=[pl.BlockSpec((1, kv, rows, hd), q_map), *pool_specs],
            out_specs=[
                pl.BlockSpec((1, kv, 1, rows, hd), out_map),
                pl.BlockSpec((1, kv, 1, rows, 1), out_map),
                pl.BlockSpec((1, kv, 1, rows, 1), out_map),
            ],
            scratch_shapes=[*dma_scratch,
                            pltpu.VMEM((kv, rows, 1), jnp.float32),
                            pltpu.VMEM((kv, rows, 1), jnp.float32),
                            pltpu.VMEM((kv, rows, hd), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, s_lanes, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, s_lanes, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, s_lanes, rows, 1), jnp.float32),
        ],
        name=NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32), q, *pools)
