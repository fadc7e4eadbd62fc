"""Pallas TPU kernel: gather-block-matmul for BlockCSR weights.

TPU adaptation of the paper's two OpenCL kernels (Figs. 2-3):

  forward : Y  = X  @ W'   (dense x compressed')   W is (N, K) BCSR
  backward: dX = dY @ W    (dense x compressed)

Both reduce to one *gather-matmul-accumulate* schedule: for each output tile
(i, o) accumulate ``D_tile(i, idx[o, j]) @ B(blk[o, j])`` over the nonzero
blocks j of output block-row o. The paper's coalesced-thread-access argument
maps onto scalar-prefetched BlockSpec index maps: the sparsity pattern lives
in SMEM-prefetched int32 tables, so the DMA engine fetches exactly the
nonzero (MXU-aligned) blocks from HBM into VMEM — contiguity by construction
rather than by thread scheduling.

The forward pass consumes the block-CSR gather tables; the backward consumes
the block-CSC (transposed) tables precomputed on host, avoiding the
uncoalesced column walk the paper accepts in its Fig. 3 kernel.

``gather_block_matmul_palette`` is the quantized-serving variant (Deep
Compression stage 2): the block store holds uint8 palette codes (nibble-
packed at 4 bits) and the per-matrix fp32 palette rides into VMEM as one
extra (1, 2**bits) operand. Dequantization is fused into the accumulate:
codes are expanded via a one-hot x palette matvec (MXU-friendly; TPU Mosaic
has no vector gather), so HBM traffic per block drops 4x/8x while the
matmul itself is unchanged.

Grid: (M/bm, O/bo, Jmax), J innermost so the output tile stays resident in
VMEM across the accumulation. Padded gather slots point at data slot 0 (an
all-zero block), so accumulating them is a no-op and the kernel needs no
dynamic trip count — branchless, which keeps the Mosaic schedule static.
A ``@pl.when(j < nnz[o])`` guard is still used to skip the matmul FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sparse.formats import unpack_uint4

LANES = 128
NAME = "bsr_spmm"      # kernel_name in HLO and profiler traces


def check_block_tiling(block, interpret: bool) -> None:
    """Mosaic's tiling rule for the compiled kernels: the last two dims of
    every BlockSpec are multiples of (8, 128) or whole array dims. The
    dense operand and output tiles are ``(bm, br)`` / ``(bm, bc)`` slices of
    wider arrays, so both block dims must be multiples of 128. Interpret
    mode has no such rule; the compiled path raises here, at trace time,
    instead of failing inside Mosaic or falling back to another backend."""
    if interpret:
        return
    br, bc = block
    if br % LANES or bc % LANES:
        raise ValueError(
            f"BCSR block {tuple(block)} cannot be tiled by the compiled "
            f"Pallas TPU kernel: Mosaic needs the last two block dims to be "
            f"multiples of (8, {LANES}), so both block dims must be "
            f"multiples of {LANES} (use --block 128 128)")


def _kernel(nnz_ref, idx_ref, blk_ref,     # scalar-prefetch (SMEM)
            d_ref, w_ref, o_ref,            # VMEM tiles
            *, transpose_block: bool, out_dtype):
    j = pl.program_id(2)
    o = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < nnz_ref[o])
    def _acc():
        d = d_ref[...]
        w = w_ref[0]                         # (br, bc) block
        if transpose_block:
            w = w.T
        o_ref[...] += jax.lax.dot(
            d.astype(jnp.float32), w.astype(jnp.float32),
            preferred_element_type=jnp.float32).astype(out_dtype)


def _palette_kernel(nnz_ref, idx_ref, blk_ref,   # scalar-prefetch (SMEM)
                    d_ref, c_ref, p_ref, o_ref,   # VMEM tiles
                    *, transpose_block: bool, bits: int, out_dtype):
    j = pl.program_id(2)
    o = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < nnz_ref[o])
    def _acc():
        d = d_ref[...]
        codes = c_ref[0].astype(jnp.int32)   # (br, bc) or (br, bc//2)
        if bits == 4:
            codes = unpack_uint4(codes)      # pure jnp — one shared copy of
                                             # the nibble-ordering convention
        # fused dequant: one-hot(codes) @ palette — a (br*bc, P) x (P,)
        # matvec instead of a vector gather (which Mosaic lacks); code 0 hits
        # palette[0] == 0 so intra-block zeros and the pad slot stay exact
        palette = p_ref[0].astype(jnp.float32)      # (P,)
        onehot = jax.nn.one_hot(codes, palette.shape[0],
                                dtype=jnp.float32)  # (br, bc, P)
        w = jax.lax.dot_general(onehot, palette,
                                (((2,), (0,)), ((), ())))
        if transpose_block:
            w = w.T
        o_ref[...] += jax.lax.dot(
            d.astype(jnp.float32), w,
            preferred_element_type=jnp.float32).astype(out_dtype)


def gather_block_matmul_palette(dense, codes, palette, idx, blk, nnz, *,
                                out_cols: int,
                                transpose_block: bool,
                                bits: int,
                                bm: int = 128,
                                out_dtype=jnp.float32,
                                interpret: bool = False):
    """Palette-quantized ``gather_block_matmul``: same schedule, the block
    store holds uint8 codes and the fp32 palette is dequantized in-kernel.

    codes   : (n_slots, br, bc) uint8 at bits=8, (n_slots, br, bc//2) at
              bits=4 (two nibble codes per byte, ``formats.pack_uint4``)
    palette : (P,) fp32 with palette[0] == 0 (P = 2**bits)
    """
    M, Kin = dense.shape
    n_slots, br, bcs = codes.shape
    bc = bcs * 2 if bits == 4 else bcs
    check_block_tiling((br, bc), interpret)
    O, jmax = idx.shape
    b_in, b_out = (bc, br) if transpose_block else (br, bc)
    assert Kin % b_in == 0 and out_cols % b_out == 0 and M % bm == 0, (
        dense.shape, codes.shape, out_cols, bm)
    assert out_cols // b_out == O

    pal2d = palette.reshape(1, -1)
    grid = (M // bm, O, jmax)

    def d_map(i, o, j, nnz_s, idx_s, blk_s):
        return (i, idx_s[o, j])

    def c_map(i, o, j, nnz_s, idx_s, blk_s):
        return (blk_s[o, j], 0, 0)

    def p_map(i, o, j, nnz_s, idx_s, blk_s):
        return (0, 0)

    def o_map(i, o, j, nnz_s, idx_s, blk_s):
        return (i, o)

    kernel = functools.partial(_palette_kernel,
                               transpose_block=transpose_block,
                               bits=bits, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, b_in), d_map),
                pl.BlockSpec((1, br, bcs), c_map),
                pl.BlockSpec((1, pal2d.shape[1]), p_map),
            ],
            out_specs=pl.BlockSpec((bm, b_out), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((M, out_cols), out_dtype),
        name="bsr_spmm_palette",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(nnz, idx, blk, dense, codes, pal2d)


def gather_block_matmul(dense, data, idx, blk, nnz, *,
                        out_cols: int,
                        transpose_block: bool,
                        bm: int = 128,
                        out_dtype=jnp.float32,
                        interpret: bool = False):
    """Y[m, o-block] = sum_j dense[m, idx[o,j]-block] @ B(blk[o,j]).

    dense : (M, Kin)  with Kin divisible by the block's inner dim
    data  : (n_slots, br, bc) BCSR block store (slot 0 = zero pad)
    idx   : (O, Jmax) int32 input-block-column table
    blk   : (O, Jmax) int32 data-slot table
    nnz   : (O,) int32 valid prefix per output block-row
    transpose_block: True for the forward X @ W' (blocks are (bo, bin) and
        need transposing); False for backward dY @ W (blocks are (bin, bo)).
    """
    M, Kin = dense.shape
    n_slots, br, bc = data.shape
    check_block_tiling((br, bc), interpret)
    O, jmax = idx.shape
    b_in, b_out = (bc, br) if transpose_block else (br, bc)
    assert Kin % b_in == 0 and out_cols % b_out == 0 and M % bm == 0, (
        dense.shape, data.shape, out_cols, bm)
    assert out_cols // b_out == O

    grid = (M // bm, O, jmax)

    def d_map(i, o, j, nnz_s, idx_s, blk_s):
        return (i, idx_s[o, j])

    def w_map(i, o, j, nnz_s, idx_s, blk_s):
        return (blk_s[o, j], 0, 0)

    def o_map(i, o, j, nnz_s, idx_s, blk_s):
        return (i, o)

    kernel = functools.partial(_kernel, transpose_block=transpose_block,
                               out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, b_in), d_map),
                pl.BlockSpec((1, br, bc), w_map),
            ],
            out_specs=pl.BlockSpec((bm, b_out), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((M, out_cols), out_dtype),
        name=NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(nnz, idx, blk, dense, data)
