"""Jit'd wrapper: fused Prox-ADAM over arbitrary param pytrees.

Each leaf is flattened, padded to a (bm, 128)-aligned 2D view, updated by the
fused kernel, and reshaped back. On TPU this is the production optimizer
path; on this CPU container it runs with interpret=True and is validated
against both ref.py and the pure-jnp optimizer in core/optimizers.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.prox import default_regularized_predicate
from repro.kernels.prox_adam.prox_adam import fused_prox_update
from repro.kernels import use_interpret
from repro.kernels.prox_adam import ref as ref_lib
_LANES = 128


def _to_tiles(x, bm):
    """Flatten to (rows, 128) with rows a multiple of bm; return view + meta."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = _LANES
    rows = -(-n // cols)
    rows = -(-rows // bm) * bm
    pad = rows * cols - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols), n


def _from_tiles(t, n, shape, dtype):
    return t.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit,
                   static_argnames=("rule", "apply_prox", "bm", "interpret"))
def _fused_update_leaf(w, g, m, v, scalars, *, rule="adam", apply_prox=True,
                       bm=256, interpret=None):
    interpret = use_interpret() if interpret is None else interpret
    wt, n = _to_tiles(w, bm)
    gt, _ = _to_tiles(g.astype(jnp.float32), bm)
    mt, _ = _to_tiles(m, bm)
    vt, _ = _to_tiles(v, bm)
    wo, mo, vo = fused_prox_update(wt, gt, mt, vt, scalars, rule=rule,
                                   apply_prox=apply_prox, bm=bm,
                                   interpret=interpret)
    return (_from_tiles(wo, n, w.shape, w.dtype),
            _from_tiles(mo, n, m.shape, jnp.float32),
            _from_tiles(vo, n, v.shape, jnp.float32))


def fused_update_leaf(w, g, m, v, scalars, *, rule="adam", apply_prox=True,
                      bm=256, interpret=None):
    return _fused_update_leaf(w, g, m, v, scalars, rule=rule,
                              apply_prox=apply_prox, bm=bm,
                              interpret=interpret)


def make_scalars(lr, lam, b1, b2, eps, t):
    """float32[8] scalar-prefetch vector; bias-correction terms precomputed."""
    t = jnp.asarray(t, jnp.float32)
    return jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(lam, jnp.float32),
                      jnp.asarray(b1, jnp.float32),
                      jnp.asarray(b2, jnp.float32),
                      jnp.asarray(eps, jnp.float32),
                      1.0 - jnp.power(jnp.asarray(b1, jnp.float32), t),
                      1.0 - jnp.power(jnp.asarray(b2, jnp.float32), t),
                      jnp.zeros((), jnp.float32)])


def fused_tree_update(params, grads, m, v, scalars, *, rule="adam",
                      predicate=None, interpret=None):
    """Whole-pytree fused update; non-regularized leaves skip the prox."""
    predicate = predicate or default_regularized_predicate
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    fg = treedef.flatten_up_to(grads)
    fm = treedef.flatten_up_to(m)
    fv = treedef.flatten_up_to(v)
    new_p, new_m, new_v = [], [], []
    for (path, p), g, mm, vv in zip(flat, fg, fm, fv):
        name = jax.tree_util.keystr(path)
        w2, m2, v2 = fused_update_leaf(p, g, mm, vv, scalars, rule=rule,
                                       apply_prox=predicate(name, p),
                                       interpret=interpret)
        new_p.append(w2)
        new_m.append(m2)
        new_v.append(v2)
    unf = jax.tree_util.tree_unflatten
    return unf(treedef, new_p), unf(treedef, new_m), unf(treedef, new_v)


fused_prox_update_ref = ref_lib.fused_prox_update_ref
