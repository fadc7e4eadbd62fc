"""Compressed sparse weight formats (paper §3.1, adapted for TPU).

The paper stores sparse weights in CSR for OpenCL kernels. On TPU the MXU
wants >= (8, 128) tiles, so the framework's first-class format is **BlockCSR
(BCSR)**: the matrix is tiled into (br, bc) blocks and only nonzero blocks are
stored. Alongside the classic (data, col_idx, row_ptr) arrays we precompute
*padded gather tables* — per output block-row, a fixed-width list of
(block-col index, data-slot index) — which are what the Pallas kernel's
scalar-prefetch index maps consume. A transposed gather table (block-CSC
view) serves the backward dense x compressed product without materializing
W^T (DESIGN.md §2: the paper pays uncoalesced access; we pay a one-time host
index sort).

A plain elementwise CSR is retained (``CSR``) as the paper-fidelity format
for size accounting and the embedded/serial reference path.

**PaletteBCSR** is the Deep-Compression stage-2 serving format (Han et al.
2016, the paper's cited follow-up): the BCSR block store holds uint8 palette
*codes* (packed two-per-byte at 4 bits) plus a per-layer fp32 palette of
2**bits values; code 0 is reserved for exact zero so intra-block sparsity
survives quantization bit-exactly. Index/gather tables are shared with
BlockCSR, so a PaletteBCSR drops into every consumer of the gather tables
(the Pallas kernel dequantizes resident blocks on the fly — palette lookup
fused into the gather-block-matmul).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@partial(jax.tree_util.register_dataclass,
         data_fields=["data", "col_idx", "row_ptr",
                      "gather_idx", "gather_blk", "gather_nnz",
                      "gather_t_idx", "gather_t_blk", "gather_t_nnz"],
         meta_fields=["shape", "block", "n_blocks"])
@dataclasses.dataclass(frozen=True)
class BlockCSR:
    """Block-CSR sparse matrix of logical ``shape`` with (br, bc) blocks.

    data:      (n_slots, br, bc) nonzero blocks, row-major over block rows.
               Slot 0 is always an all-zero pad block; real blocks start at 1,
               so padded gather entries can point at slot 0 harmlessly.
    col_idx:   (n_slots,) int32 block-column of each slot (0 for the pad).
    row_ptr:   (R+1,) int32 CSR pointers into slots 1..n_blocks.
    gather_*:  (R, Jmax) padded per-block-row tables driving the forward
               kernel; gather_nnz (R,) gives the valid prefix length.
    gather_t_*: the block-CSC (transposed) tables, (C, Jmax_t), for backward.
    """
    data: Array
    col_idx: Array
    row_ptr: Array
    gather_idx: Array
    gather_blk: Array
    gather_nnz: Array
    gather_t_idx: Array
    gather_t_blk: Array
    gather_t_nnz: Array
    shape: tuple[int, int]
    block: tuple[int, int]
    n_blocks: int

    @property
    def block_grid(self) -> tuple[int, int]:
        br, bc = self.block
        return (-(-self.shape[0] // br), -(-self.shape[1] // bc))

    @property
    def nnz(self) -> int:
        return self.n_blocks * self.block[0] * self.block[1]

    @property
    def nbytes(self) -> int:
        return sum(int(x.size) * x.dtype.itemsize
                   for x in (self.data, self.col_idx, self.row_ptr))

    def to_dense(self) -> Array:
        return bcsr_to_dense(self)


def dense_to_bcsr(w, block: tuple[int, int] = (128, 128),
                  pad_rows_to_multiple: bool = True) -> BlockCSR:
    """Convert a dense 2D array to BlockCSR, keeping blocks with any nonzero.

    Host-side (numpy): format construction happens at checkpoint-load /
    compression time, never inside a jitted step.
    """
    w = np.asarray(w)
    assert w.ndim == 2, w.shape
    br, bc = block
    r, c = w.shape
    pr, pc = (-r) % br, (-c) % bc
    if (pr or pc):
        if not pad_rows_to_multiple:
            raise ValueError(f"shape {w.shape} not divisible by block {block}")
        w = np.pad(w, ((0, pr), (0, pc)))
    R, C = w.shape[0] // br, w.shape[1] // bc
    wb = w.reshape(R, br, C, bc).transpose(0, 2, 1, 3)  # (R, C, br, bc)
    nz = np.any(wb != 0, axis=(2, 3))                   # (R, C) block occupancy

    rows, cols = np.nonzero(nz)                         # row-major order
    n_blocks = len(rows)
    data = np.zeros((n_blocks + 1, br, bc), dtype=w.dtype)
    data[1:] = wb[rows, cols]
    col_idx = np.zeros(n_blocks + 1, dtype=np.int32)
    col_idx[1:] = cols
    row_ptr = np.zeros(R + 1, dtype=np.int32)
    np.add.at(row_ptr, rows + 1, 1)
    row_ptr = np.cumsum(row_ptr).astype(np.int32)

    # forward gather tables (per block-row)
    jmax = max(int(np.max(row_ptr[1:] - row_ptr[:-1])), 1) if R else 1
    g_idx = np.zeros((R, jmax), np.int32)
    g_blk = np.zeros((R, jmax), np.int32)
    gn = np.zeros(R, np.int32)
    for rr in range(R):
        lo, hi = row_ptr[rr], row_ptr[rr + 1]
        g_idx[rr, :hi - lo] = cols[lo:hi]
        g_blk[rr, :hi - lo] = np.arange(lo + 1, hi + 1)  # +1: slot 0 is the pad
        gn[rr] = hi - lo
    g_nnz = gn

    # transposed (block-CSC) gather tables (per block-col)
    order = np.lexsort((rows, cols))
    t_rows, t_cols, t_slots = rows[order], cols[order], order + 1
    tn = np.zeros(C, np.int32)
    np.add.at(tn, t_cols, 1)
    jmax_t = max(int(tn.max()) if C else 1, 1)
    t_idx = np.zeros((C, jmax_t), np.int32)
    t_blk = np.zeros((C, jmax_t), np.int32)
    fill = np.zeros(C, np.int32)
    for rr, cc, ss in zip(t_rows, t_cols, t_slots):
        t_idx[cc, fill[cc]] = rr
        t_blk[cc, fill[cc]] = ss
        fill[cc] += 1

    dev = jnp.asarray
    return BlockCSR(
        data=dev(data), col_idx=dev(col_idx), row_ptr=dev(row_ptr),
        gather_idx=dev(g_idx), gather_blk=dev(g_blk), gather_nnz=dev(g_nnz),
        gather_t_idx=dev(t_idx), gather_t_blk=dev(t_blk), gather_t_nnz=dev(tn),
        shape=(r, c), block=(br, bc), n_blocks=n_blocks)


def bcsr_to_dense(m: BlockCSR) -> Array:
    """Pure-jnp densification (jit-safe): scatter blocks back."""
    br, bc = m.block
    R, C = m.block_grid
    dense_blocks = jnp.zeros((R, C, br, bc), m.data.dtype)
    # slot s (>=1) belongs to block-row found from row_ptr; precompute rows on
    # host is not possible here (jit-safe path), so rebuild from gather tables.
    rr = jnp.repeat(jnp.arange(R), m.gather_idx.shape[1])
    cc = m.gather_idx.reshape(-1)
    ss = m.gather_blk.reshape(-1)
    blocks = m.data[ss]                      # (R*Jmax, br, bc); pad slots give 0
    dense_blocks = dense_blocks.at[rr, cc].add(blocks)
    return dense_blocks.transpose(0, 2, 1, 3).reshape(R * br, C * bc)


def bcsr_density(m: BlockCSR) -> float:
    R, C = m.block_grid
    return m.n_blocks / max(R * C, 1)


def pad_bcsr(m: BlockCSR, n_slots: int, jmax: int, jmax_t: int) -> BlockCSR:
    """Pad a BlockCSR's slot store and gather tables to fixed widths.

    Extra slots are zero blocks and extra gather columns point at slot 0 (the
    pad), so the kernel's output is unchanged. This makes BCSRs of different
    sparsity patterns shape-compatible, which is what lets per-layer
    compressed weights be ``jnp.stack``-ed and ridden through the layer-stack
    ``lax.scan`` (see sparse/compress.py). ``n_blocks`` is set to the padded
    slot count so the stacked metas compare equal; ``nbytes`` then reports
    the bytes actually stored.
    """
    cur_slots = m.data.shape[0]
    cur_j, cur_jt = m.gather_idx.shape[1], m.gather_t_idx.shape[1]
    assert n_slots >= cur_slots and jmax >= cur_j and jmax_t >= cur_jt, (
        (n_slots, jmax, jmax_t), (cur_slots, cur_j, cur_jt))

    def pad0(a, widths):
        return jnp.pad(a, widths)

    return BlockCSR(
        data=pad0(m.data, ((0, n_slots - cur_slots), (0, 0), (0, 0))),
        col_idx=pad0(m.col_idx, ((0, n_slots - cur_slots),)),
        row_ptr=m.row_ptr,
        gather_idx=pad0(m.gather_idx, ((0, 0), (0, jmax - cur_j))),
        gather_blk=pad0(m.gather_blk, ((0, 0), (0, jmax - cur_j))),
        gather_nnz=m.gather_nnz,
        gather_t_idx=pad0(m.gather_t_idx, ((0, 0), (0, jmax_t - cur_jt))),
        gather_t_blk=pad0(m.gather_t_blk, ((0, 0), (0, jmax_t - cur_jt))),
        gather_t_nnz=m.gather_t_nnz,
        shape=m.shape, block=m.block, n_blocks=n_slots - 1)


# ---------------------------------------------------------------------------
# PaletteBCSR — quantized block store (Deep Compression stage 2)
# ---------------------------------------------------------------------------

def pack_uint4(codes):
    """Pack uint8 codes < 16 two-per-byte along the last axis (must be even).

    Convention: with n = codes.shape[-1], byte k holds codes[k] in the low
    nibble and codes[k + n/2] in the high nibble, so unpacking is a
    concatenation of the two nibble planes (which Mosaic compiles; an
    interleave is an unsupported shape cast) and
    ``unpack_uint4(pack_uint4(c)) == c``.
    """
    assert codes.shape[-1] % 2 == 0, codes.shape
    c = jnp.asarray(codes, jnp.uint8)
    half = c.shape[-1] // 2
    return (c[..., :half] | (c[..., half:] << 4)).astype(jnp.uint8)


def unpack_uint4(packed):
    """Inverse of ``pack_uint4``: (..., n) -> (..., 2n) codes, in the
    integer dtype of ``packed`` (uint8 storage, or int32 inside a kernel)."""
    p = jnp.asarray(packed)
    return jnp.concatenate([p & 0xF, p >> 4], axis=-1)


def dequantize_codes(codes, palette, bits: int):
    """Palette lookup: codes (uint8, possibly nibble-packed) -> fp blocks.

    ``palette`` is (P,) for a single matrix, (L, P) for a stacked layer
    store, or (L, E, P) for a per-expert MoE stack (``codes`` carries the
    matching leading axes). jit-safe.
    """
    if bits == 4:
        codes = unpack_uint4(codes)

    def take(c, p):
        return jnp.take(p, c.astype(jnp.int32))

    if palette.ndim == 1:
        return take(codes, palette)
    lead = palette.shape[:-1]                   # stacked layer/expert axes
    cf = codes.reshape((-1,) + codes.shape[len(lead):])
    pf = palette.reshape(-1, palette.shape[-1])
    return jax.vmap(take)(cf, pf).reshape(codes.shape)


@partial(jax.tree_util.register_dataclass,
         data_fields=["codes", "palette", "col_idx", "row_ptr",
                      "gather_idx", "gather_blk", "gather_nnz",
                      "gather_t_idx", "gather_t_blk", "gather_t_nnz"],
         meta_fields=["shape", "block", "n_blocks", "bits"])
@dataclasses.dataclass(frozen=True)
class PaletteBCSR:
    """Palette-quantized BlockCSR: same index/gather structure as
    ``BlockCSR``, block data stored as palette codes.

    codes:   (n_slots, br, bc) uint8 at bits=8, (n_slots, br, bc//2) uint8
             with two nibble codes per byte at bits=4. Slot 0 stays the
             all-zero pad block (all codes 0).
    palette: (2**bits,) fp32 values; palette[0] == 0.0 exactly, so code 0
             reproduces intra-block zeros bit-exactly and the sparsity
             pattern is invariant under quantization.
    bits:    4 or 8 (static metadata — selects the kernel unpack path).

    Stacked layer stores carry a leading ``n_super`` axis on every array
    field (codes (L, n_slots, br, bc'), palette (L, 2**bits), ...), exactly
    like a stacked ``BlockCSR``, so the quantized stack rides through the
    layer ``lax.scan`` unchanged.
    """
    codes: Array
    palette: Array
    col_idx: Array
    row_ptr: Array
    gather_idx: Array
    gather_blk: Array
    gather_nnz: Array
    gather_t_idx: Array
    gather_t_blk: Array
    gather_t_nnz: Array
    shape: tuple[int, int]
    block: tuple[int, int]
    n_blocks: int
    bits: int

    @property
    def block_grid(self) -> tuple[int, int]:
        br, bc = self.block
        return (-(-self.shape[0] // br), -(-self.shape[1] // bc))

    @property
    def nnz(self) -> int:
        return self.n_blocks * self.block[0] * self.block[1]

    @property
    def nbytes(self) -> int:
        """Actual serving bytes: packed codes + palette + block indices.
        (codes are already nibble-packed at bits=4, so .size counts bytes)."""
        return sum(int(x.size) * x.dtype.itemsize
                   for x in (self.codes, self.palette,
                             self.col_idx, self.row_ptr))

    @property
    def bcsr_equiv_nbytes(self) -> int:
        """Bytes the same blocks would take as an unquantized fp32 BlockCSR
        (the denominator of the stage-2 compression ratio)."""
        n_entries = int(self.codes.size) * (2 if self.bits == 4 else 1)
        return n_entries * 4 + int(self.col_idx.size) * 4 \
            + int(self.row_ptr.size) * 4

    def dequantize(self) -> BlockCSR:
        """Expand to an fp BlockCSR with identical index/gather tables."""
        return BlockCSR(
            data=dequantize_codes(self.codes, self.palette, self.bits),
            col_idx=self.col_idx, row_ptr=self.row_ptr,
            gather_idx=self.gather_idx, gather_blk=self.gather_blk,
            gather_nnz=self.gather_nnz,
            gather_t_idx=self.gather_t_idx, gather_t_blk=self.gather_t_blk,
            gather_t_nnz=self.gather_t_nnz,
            shape=self.shape, block=self.block, n_blocks=self.n_blocks)

    def to_dense(self) -> Array:
        return bcsr_to_dense(self.dequantize())


# ---------------------------------------------------------------------------
# Elementwise CSR (paper-fidelity reference format)
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=["data", "indices", "indptr"], meta_fields=["shape"])
@dataclasses.dataclass(frozen=True)
class CSR:
    """Paper Fig. 1(iii): ptr/indices/data elementwise CSR."""
    data: Array
    indices: Array
    indptr: Array
    shape: tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    @property
    def nbytes(self) -> int:
        return (self.data.size * self.data.dtype.itemsize
                + self.indices.size * 4 + self.indptr.size * 4)


def dense_to_csr(w) -> CSR:
    w = np.asarray(w)
    assert w.ndim == 2
    rows, cols = np.nonzero(w)
    indptr = np.zeros(w.shape[0] + 1, np.int32)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return CSR(data=jnp.asarray(w[rows, cols]),
               indices=jnp.asarray(cols.astype(np.int32)),
               indptr=jnp.asarray(indptr), shape=tuple(w.shape))


def csr_to_dense(m: CSR) -> Array:
    out = jnp.zeros(m.shape, m.data.dtype)
    nptr = np.asarray(m.indptr)
    rows = np.repeat(np.arange(m.shape[0]), nptr[1:] - nptr[:-1])
    return out.at[jnp.asarray(rows), m.indices].set(m.data)
