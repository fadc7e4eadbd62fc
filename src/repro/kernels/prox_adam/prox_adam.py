"""Pallas TPU kernel: fused Prox-ADAM / Prox-RMSProp update.

TPU analogue of the paper's elementwise prox OpenCL kernel (Fig. 4), fused
with the full optimizer update. Unfused, one ADAM+prox step reads/writes each
of (w, g, m, v) several times through HBM; fused, each tensor crosses HBM
exactly once per direction — the update is purely memory-bound, so fusion is
worth ~4-7x on the optimizer step (see EXPERIMENTS.md §Perf napkin math).

Scalars (lr, lambda, t and the betas' running powers) arrive via scalar
prefetch in SMEM so one compiled kernel serves every step.

Layout: params are flattened and tiled to (rows, LANE)= (8k, 128)-aligned 2D
blocks by ops.py; the kernel itself is shape-agnostic over (bm, 128*q) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(sc_ref,                      # (8,) scalar prefetch
            w_ref, g_ref, m_ref, v_ref,  # inputs (VMEM)
            wo_ref, mo_ref, vo_ref,      # outputs (VMEM)
            *, rule: str, apply_prox: bool):
    lr = sc_ref[0]
    lam = sc_ref[1]
    b1 = sc_ref[2]
    b2 = sc_ref[3]
    eps = sc_ref[4]
    bc1 = sc_ref[5]   # 1 - b1**t  (bias-correction denominators, host side)
    bc2 = sc_ref[6]   # 1 - b2**t

    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...]

    if rule == "adam":
        m = m_ref[...]
        m2 = b1 * m + (1.0 - b1) * g
        v2 = b2 * v + (1.0 - b2) * g * g
        mhat = m2 / bc1
        vhat = v2 / bc2
        d = mhat / (jnp.sqrt(vhat) + eps)
        mo_ref[...] = m2
    elif rule == "rmsprop":
        v2 = b2 * v + (1.0 - b2) * g * g
        d = g / (jnp.sqrt(v2) + eps)
        mo_ref[...] = m_ref[...]
    else:
        raise ValueError(rule)

    z = w - lr * d
    if apply_prox:
        tau = lr * lam
        # paper Fig. 4 min/max form of soft thresholding
        z = jnp.minimum(jnp.maximum(z - tau, 0.0), z + tau)
    wo_ref[...] = z.astype(wo_ref.dtype)
    vo_ref[...] = v2


def fused_prox_update(w, g, m, v, scalars, *, rule: str = "adam",
                      apply_prox: bool = True, bm: int = 256,
                      interpret: bool = False):
    """One fused optimizer+prox step over a 2D (rows, 128k)-shaped view.

    scalars: float32[8] = [lr, lam, b1, b2, eps, 1-b1^t, 1-b2^t, pad].
    Returns (w', m', v').
    """
    rows, cols = w.shape
    assert rows % bm == 0 and cols % 128 == 0, (w.shape, bm)
    grid = (rows // bm,)

    def tile(i, sc):
        return (i, 0)

    kern = functools.partial(_kernel, rule=rule, apply_prox=apply_prox)
    spec = pl.BlockSpec((bm, cols), tile)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec, spec, spec, spec],
            out_specs=[spec,
                       pl.BlockSpec((bm, cols), tile),
                       pl.BlockSpec((bm, cols), tile)],
        ),
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(scalars, w, g, m, v)
    return out
