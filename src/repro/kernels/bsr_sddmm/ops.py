"""Jit'd wrapper: masked BCSR weight gradient from a BlockCSR structure.

``bsr_weight_grad(x, dy, w)`` -> (n_slots, br, bc) gradient blocks aligned
with ``w.data`` (slot 0, the pad, is zero), i.e. a drop-in gradient for the
compressed weight store during mask-frozen (debias) retraining.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bsr_sddmm.bsr_sddmm import sddmm_block_grad
from repro.kernels.bsr_sddmm import ref as ref_lib
from repro.kernels import use_interpret
from repro.sparse.formats import BlockCSR, PaletteBCSR


def _reject_palette(w):
    """Palette-quantized weights are a serving-only format: the SDDMM weight
    gradient targets fp block data, which a code/palette store doesn't have.
    Mask-frozen (debias) retraining must run on the BlockCSR form BEFORE
    quantization (``sparse.compress.quantize_compressed`` is the last
    pipeline stage; ``dequantize_compressed`` goes back if needed)."""
    if isinstance(w, PaletteBCSR):
        raise TypeError(
            "bsr_weight_grad got a PaletteBCSR: quantized weights are not "
            "trainable — debias before quantize_compressed(), or "
            "dequantize_compressed() to resume retraining")


def slot_coordinates(w: BlockCSR):
    """Per-slot (block-row, block-col, valid) vectors, derived jit-safely
    from the gather tables (slot 0 keeps (0, 0)).

    ``valid`` marks slots actually referenced by a gather entry: the pad
    slot 0 and any trailing slots added by ``formats.pad_bcsr`` (empty /
    fully-pruned layers padded up to a stacked slot count) are invalid and
    must carry zero gradient — without the mask they would silently pick up
    the (0, 0) block's gradient."""
    n_slots = w.data.shape[0]
    r_grid = w.gather_idx.shape[0]
    rows_src = jnp.repeat(jnp.arange(r_grid, dtype=jnp.int32),
                          w.gather_idx.shape[1])
    slots = w.gather_blk.reshape(-1)
    rows = jnp.zeros((n_slots,), jnp.int32).at[slots].set(rows_src)
    cols = jnp.zeros((n_slots,), jnp.int32).at[slots].set(
        w.gather_idx.reshape(-1).astype(jnp.int32))
    valid = jnp.zeros((n_slots,), bool).at[slots].set(True).at[0].set(False)
    return rows.at[0].set(0), cols.at[0].set(0), valid


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _bsr_weight_grad(x, dy, w: BlockCSR, *, bm: int = 128,
                     interpret: bool | None = None):
    interpret = use_interpret() if interpret is None else interpret
    br, bc = w.block
    m = x.shape[0]
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
    # pad feature dims to the block grid
    n_pad = w.block_grid[0] * br
    k_pad = w.block_grid[1] * bc
    if dy.shape[1] != n_pad:
        dy = jnp.pad(dy, ((0, 0), (0, n_pad - dy.shape[1])))
    if x.shape[1] != k_pad:
        x = jnp.pad(x, ((0, 0), (0, k_pad - x.shape[1])))
    rows, cols, valid = slot_coordinates(w)
    out = sddmm_block_grad(dy, x, rows, cols, w.data.shape[0], br, bc,
                           bm=bm, interpret=interpret)
    # pad slots (slot 0 + pad_bcsr padding) carry no gradient
    return out * valid[:, None, None].astype(out.dtype)


def bsr_weight_grad(x, dy, w: BlockCSR, *, bm: int = 128,
                    interpret: bool | None = None):
    """x: (M, K) activations; dy: (M, N) output cotangent; w: (N, K) BCSR.

    Returns (n_slots, br, bc) f32 gradient blocks for w.data."""
    _reject_palette(w)
    return _bsr_weight_grad(x, dy, w, bm=bm, interpret=interpret)


def bsr_weight_grad_ref(x, dy, w: BlockCSR):
    _reject_palette(w)
    rows, cols, valid = slot_coordinates(w)
    br, bc = w.block
    n_pad = w.block_grid[0] * br
    k_pad = w.block_grid[1] * bc
    dy = jnp.pad(dy, ((0, 0), (0, n_pad - dy.shape[1])))
    x = jnp.pad(x, ((0, 0), (0, k_pad - x.shape[1])))
    out = ref_lib.sddmm_block_grad_ref(dy, x, rows, cols,
                                       w.data.shape[0], br, bc)
    return out * valid[:, None, None].astype(out.dtype)
