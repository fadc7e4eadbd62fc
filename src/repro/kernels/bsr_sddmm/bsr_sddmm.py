"""Pallas TPU kernel: sampled dense-dense matmul (SDDMM) for BCSR weight
gradients.

Completes the paper's compressed-training kernel triad: forward
(dense x compressed'), backward-data (dense x compressed), and this —
backward-weights, computed ONLY at the surviving (nonzero) blocks:

    dW[block b at (r, c)] = dY[:, r-block]^T @ X[:, c-block]

During debias retraining (paper §2.4) the zero pattern is frozen, so a
dense (N, K) dW is pure waste at 90%+ sparsity: this kernel produces the
(n_slots, br, bc) block store directly — FLOPs and HBM bytes scale with
nnz blocks, not N*K. Grid: (n_slots, M/bm) with M innermost so each block's
accumulator stays VMEM-resident; per-slot (row, col) indices arrive via
scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsr_spmm.bsr_spmm import check_block_tiling


def _kernel(rows_ref, cols_ref, dy_ref, x_ref, out_ref, *, n_m):
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += jax.lax.dot_general(
        dy_ref[...].astype(jnp.float32), x_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),           # contract over the M dimension
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def sddmm_block_grad(dy, x, slot_rows, slot_cols, n_slots: int,
                     br: int, bc: int, *, bm: int = 128,
                     out_dtype=jnp.float32, interpret: bool = False):
    """dy: (M, N), x: (M, K); returns (n_slots, br, bc) block gradients.

    slot_rows/slot_cols: int32[n_slots] block coordinates per slot (slot 0
    is the BCSR pad slot; the wrapper zeroes its output).
    """
    check_block_tiling((br, bc), interpret)
    m_dim = dy.shape[0]
    assert m_dim % bm == 0 and m_dim == x.shape[0]
    grid = (n_slots, m_dim // bm)

    def dy_map(s, m, rows, cols):
        return (m, rows[s])

    def x_map(s, m, rows, cols):
        return (m, cols[s])

    def out_map(s, m, rows, cols):
        return (s, 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, n_m=m_dim // bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, br), dy_map),
                pl.BlockSpec((bm, bc), x_map),
            ],
            out_specs=pl.BlockSpec((1, br, bc), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, br, bc), out_dtype),
        name="bsr_sddmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(slot_rows, slot_cols, dy, x)
