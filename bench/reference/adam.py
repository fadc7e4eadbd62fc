"""Plain Adam (with bias correction) over the reference's dense weights.

Debias retraining keeps the zero pattern frozen: the gradient of a weight
outside the kept blocks is dropped and the weight stays zero. Norm scales
and the tied embedding train unmasked.

Memory is what bounds this on one chip (weights, two moments and a
gradient of a dense model in float32): the update runs leaf by leaf in
place, the frozen pattern is kept as booleans, and the starting weights
wait on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _frozen(path) -> bool:
    name = jax.tree_util.keystr(path)
    return not ("scale" in name or "embed" in name)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _update(w, g, m, v, mask, t, lr, b1, b2, eps):
    g = jnp.where(mask, g, 0.0)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    w = jnp.where(mask, w - lr * mhat / (jnp.sqrt(vhat) + eps), 0.0)
    return w, m, v


def steps(weights, grad_fn, batches, lr: float, b1: float = 0.9,
          b2: float = 0.999, eps: float = 1e-8) -> dict:
    """Run ``len(batches)`` Adam steps from ``weights`` (consumed).
    ``grad_fn(weights, batch) -> (loss, grads)``. Returns the losses, and
    per leaf (by key path) the first masked gradient's norm and the norm
    of the weights' change over the steps."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(weights)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    w = [x for _, x in flat]
    masks = [(x != 0) if _frozen(p) else jnp.ones(x.shape, bool)
             for p, x in flat]
    w0 = [np.asarray(jax.device_get(x)) for x in w]
    m = [jnp.zeros_like(x) for x in w]
    v = [jnp.zeros_like(x) for x in w]
    del weights, flat
    losses, g_norms = [], None
    for t, batch in enumerate(batches, start=1):
        loss, g = grad_fn(jax.tree_util.tree_unflatten(treedef, w), batch)
        losses.append(float(loss))
        g = jax.tree.leaves(g)
        if g_norms is None:
            g_norms = [float(jnp.linalg.norm(jnp.where(k, x, 0.0)))
                       for x, k in zip(g, masks)]
        for i in range(len(w)):
            w[i], m[i], v[i] = _update(w[i], g[i], m[i], v[i], masks[i],
                                       float(t), lr, b1, b2, eps)
            g[i] = None
    d_norms = [float(np.linalg.norm(np.asarray(jax.device_get(a)) - b))
               for a, b in zip(w, w0)]
    return {"losses": losses,
            "leaves": {n: (gn, dn) for n, gn, dn in zip(names, g_norms,
                                                        d_norms)},
            "shapes": {n: x.shape for n, x in zip(names, w0)}}
