"""Jit'd public wrappers for the BCSR spmm kernel, with custom VJP.

``spmm(x, w)`` computes x @ w.T for a BlockCSR ``w`` (the paper's forward
dense x compressed'); its VJP reuses the same kernel with the transposed
gather tables (dense x compressed) for dx, and densifies only for dw (dw is
produced for the *training* path where w is still dense; the BCSR path is
the serving path, so dw is rarely needed — see models/layers.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bsr_spmm.bsr_spmm import (gather_block_matmul,
                                             gather_block_matmul_palette)
from repro.kernels.bsr_spmm import ref as ref_lib
from repro.kernels import use_interpret
from repro.sparse.formats import BlockCSR, PaletteBCSR


def _pad_rows(x, bm):
    m = x.shape[0]
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _spmm(x, w: BlockCSR, *, bm: int = 128, interpret: bool | None = None):
    interpret = use_interpret() if interpret is None else interpret
    n, k = w.shape
    xp, m = _pad_rows(x, bm)
    k_pad = w.block_grid[1] * w.block[1]
    if k_pad != xp.shape[1]:
        xp = jnp.pad(xp, ((0, 0), (0, k_pad - xp.shape[1])))
    y = gather_block_matmul(xp, w.data, w.gather_idx, w.gather_blk,
                            w.gather_nnz, out_cols=w.block_grid[0] * w.block[0],
                            transpose_block=True, bm=bm, interpret=interpret)
    return y[:m, :n]


def spmm(x, w: BlockCSR, *, bm: int = 128, interpret: bool | None = None):
    """Y (M, N) = X (M, K) @ W' for W (N, K) BlockCSR."""
    return _spmm(x, w, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _spmm_t(dy, w: BlockCSR, *, bm: int = 128, interpret: bool | None = None):
    interpret = use_interpret() if interpret is None else interpret
    n, k = w.shape
    dyp, m = _pad_rows(dy, bm)
    # pad N up to the block grid (gather tables index padded block rows)
    br, bc = w.block
    n_pad = w.block_grid[0] * br
    if n_pad != dyp.shape[1]:
        dyp = jnp.pad(dyp, ((0, 0), (0, n_pad - dyp.shape[1])))
    dx = gather_block_matmul(dyp, w.data, w.gather_t_idx, w.gather_t_blk,
                             w.gather_t_nnz, out_cols=w.block_grid[1] * bc,
                             transpose_block=False, bm=bm, interpret=interpret)
    return dx[:m, :k]


def spmm_t(dy, w: BlockCSR, *, bm: int = 128, interpret: bool | None = None):
    """dX (M, K) = dY (M, N) @ W for W (N, K) BlockCSR (backward)."""
    return _spmm_t(dy, w, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _spmm_palette(x, w: PaletteBCSR, *, bm: int = 128,
                  interpret: bool | None = None):
    interpret = use_interpret() if interpret is None else interpret
    n, k = w.shape
    xp, m = _pad_rows(x, bm)
    k_pad = w.block_grid[1] * w.block[1]
    if k_pad != xp.shape[1]:
        xp = jnp.pad(xp, ((0, 0), (0, k_pad - xp.shape[1])))
    y = gather_block_matmul_palette(
        xp, w.codes, w.palette, w.gather_idx, w.gather_blk, w.gather_nnz,
        out_cols=w.block_grid[0] * w.block[0], transpose_block=True,
        bits=w.bits, bm=bm, interpret=interpret)
    return y[:m, :n]


def spmm_palette(x, w: PaletteBCSR, *, bm: int = 128,
                 interpret: bool | None = None):
    """Y (M, N) = X (M, K) @ W' for W (N, K) PaletteBCSR — the quantized
    serving forward. Dequantization (palette lookup, nibble unpack at 4-bit)
    is fused into the gather-block-matmul kernel."""
    return _spmm_palette(x, w, bm=bm, interpret=interpret)


@jax.custom_vjp
def spmm_ad(x, w: BlockCSR):
    """Differentiable-in-x spmm (w is a constant serving-time structure)."""
    return spmm(x, w)


def _fwd(x, w):
    return spmm(x, w), w


def _bwd(w, dy):
    return spmm_t(dy, w), None


spmm_ad.defvjp(_fwd, _bwd)

# re-exported oracles for tests/benches
spmm_fwd_ref = ref_lib.spmm_fwd_ref
spmm_bwd_ref = ref_lib.spmm_bwd_ref
spmm_palette_fwd_ref = ref_lib.spmm_palette_fwd_ref
