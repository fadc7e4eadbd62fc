"""Sparse op dispatch: Pallas kernel vs pure-jnp reference.

``sparse_matmul(x, w)`` is the matmul on compressed weights, used by BOTH the
serving path (forward only) and SpC-Retrain (paper §2.4, compressed
retraining). It carries a full ``custom_vjp``:

  forward   y  = x @ W'      (dense x compressed', bsr_spmm kernel)
  backward  dx = dy @ W      (dense x compressed, transposed gather tables)
            dw = SDDMM       (kernels/bsr_sddmm: gradients ONLY at the
                              resident BCSR slots — never a dense (out, in)
                              materialization, so compressed retraining's
                              FLOPs/bytes scale with nnz blocks)

Backend selection (shared by forward and backward so serve and train hit the
same kernel — ``resolve_backend`` is the single point of truth):
  'pallas'    — the TPU kernels (interpret mode on CPU),
  'ref'       — densify + jnp for the spmm products (oracle; fastest on CPU).
                The dw product still goes through the SDDMM kernel: the ref
                spmm densifies the *weight*, but the weight *gradient* is
                never materialized dense on any backend,
  'auto'      — pallas on TPU, ref elsewhere.

``sparse_matmul`` also accepts a ``PaletteBCSR`` (palette-quantized block
store, Deep Compression stage 2): the forward dequantizes resident blocks on
the fly — in-kernel on the pallas backend, dequantize-then-matmul on ref.
Serving-only: no weight gradient is defined for the quantized form.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import run_replicated
from repro.kernels.bsr_sddmm import ops as sddmm_kops
from repro.kernels.bsr_spmm import ops as kops
from repro.sparse.formats import BlockCSR, PaletteBCSR


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    """'auto' -> pallas on TPU, ref elsewhere; validates explicit choices.

    Both ``sparse_matmul`` and ``sparse_matmul_t`` (and the custom VJP that
    ties them together) resolve through here, so the forward serving kernel
    and the training backward always agree."""
    if backend == "auto":
        return "pallas" if _on_tpu() else "ref"
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown sparse backend {backend!r}")
    return backend


def _fwd_product(x, w: BlockCSR, backend: str):
    if backend == "pallas":
        return run_replicated(kops.spmm, x, w)
    return kops.spmm_fwd_ref(x, w).astype(x.dtype)


def _bwd_dx_product(dy, w: BlockCSR, backend: str):
    if backend == "pallas":
        return run_replicated(kops.spmm_t, dy, w)
    return kops.spmm_bwd_ref(dy, w).astype(dy.dtype)


def _zero_cotangent(a):
    """Zero cotangent for a BlockCSR side array (float0 for int indices)."""
    if jnp.issubdtype(a.dtype, jnp.inexact):
        return jnp.zeros_like(a)
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sparse_matmul(backend: str, x, w: BlockCSR):
    return _fwd_product(x, w, backend)


def _sparse_matmul_fwd(backend, x, w):
    return _fwd_product(x, w, backend), (x, w)


def _sparse_matmul_bwd(backend, res, dy):
    x, w = res
    dx = _bwd_dx_product(dy, w, backend).astype(x.dtype)
    # dw via SDDMM at the resident slots only: (n_slots, br, bc) aligned
    # with w.data. The kernel runs in interpret mode off-TPU; there is no
    # dense (out, in) intermediate on any backend.
    dw_data = run_replicated(sddmm_kops.bsr_weight_grad, x, dy,
                             w).astype(w.data.dtype)
    # zero cotangents for every side array (float0 for int indices), real
    # gradient only at the block data — tree.map keeps the field list in
    # one place (the dataclass registration)
    dw = dataclasses.replace(jax.tree.map(_zero_cotangent, w), data=dw_data)
    return dx, dw


_sparse_matmul.defvjp(_sparse_matmul_fwd, _sparse_matmul_bwd)


def _palette_fwd_product(x, w: PaletteBCSR, backend: str):
    if backend == "pallas":
        return run_replicated(kops.spmm_palette, x, w)
    return kops.spmm_palette_fwd_ref(x, w).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _palette_matmul(backend: str, x, w: PaletteBCSR):
    return _palette_fwd_product(x, w, backend)


def _palette_matmul_fwd(backend, x, w):
    return _palette_fwd_product(x, w, backend), (x, w)


def _palette_matmul_bwd(backend, res, dy):
    x, w = res
    # dx through the dequantized weight — defined on BOTH backends so CPU
    # tests and TPU serving agree (the raw pallas_call has no VJP). The
    # quantized weight itself is a serving-time constant: codes/indices are
    # ints and the palette deliberately gets a zero cotangent — retraining
    # must go through dequantize_compressed().
    dx = _bwd_dx_product(dy, w.dequantize(), backend).astype(x.dtype)
    return dx, jax.tree.map(_zero_cotangent, w)


_palette_matmul.defvjp(_palette_matmul_fwd, _palette_matmul_bwd)


def sparse_matmul(x, w, backend: str = "auto"):
    """y = x @ w.T for compressed w (paper forward dense x compressed').

    ``w`` is a ``BlockCSR`` or a ``PaletteBCSR`` (Deep Compression stage 2;
    palette lookup fused into the kernel). The BlockCSR path is
    differentiable in x (dense x compressed backward) AND in w.data (SDDMM
    masked weight gradient) — the compressed-retraining path. PaletteBCSR is
    a *serving-only* weight format: differentiable in x on both backends
    (dx through the dequantized weight), but w is treated as a constant —
    quantize after debias (``sparse.compress.quantize_compressed``), or
    ``dequantize_compressed`` to resume retraining."""
    if isinstance(w, PaletteBCSR):
        return _palette_matmul(resolve_backend(backend), x, w)
    return _sparse_matmul(resolve_backend(backend), x, w)


def sparse_matmul_t(dy, w, backend: str = "auto"):
    """dx = dy @ w (paper backward dense x compressed). A ``PaletteBCSR``
    is dequantized first (same product the palette VJP's dx path uses)."""
    if isinstance(w, PaletteBCSR):
        w = w.dequantize()
    return _bwd_dx_product(dy, w, resolve_backend(backend))
