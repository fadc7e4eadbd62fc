"""Seeded block-sparse weight arithmetic shared by every architecture.

The benchmark makes its own weights, so the plain reference can take them
without taking anything the program made. Each architecture's ``weights``
(``bench/arch/``) draws its leaves from the seed with these helpers: every
projection it compresses has exactly the share of zero blocks the
configuration states, so the program's own compression finds those zero
blocks and nothing is pruned by magnitude.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def kept_blocks(out: int, inp: int, block, sparsity: float) -> tuple:
    """(grid rows, grid cols, kept) of one (out, in) matrix: the same count
    the program's magnitude pruning keeps at this sparsity."""
    r, c = -(-out // block[0]), -(-inp // block[1])
    return r, c, r * c - int(round(sparsity * r * c))


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (64-bit seeds too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def block_sparse(key, L, out, inp, block, sparsity):
    """(L, out, in) f32 with exactly ``kept`` nonzero blocks per layer.

    Kept blocks are drawn N(0, 1 / (fan_in * density)): the projection keeps
    unit gain at any sparsity."""
    r, c, kept = kept_blocks(out, inp, block, sparsity)
    k_pick, k_val = jax.random.split(key)
    scores = jax.random.uniform(k_pick, (L, r * c))
    rank = jnp.argsort(jnp.argsort(scores, axis=-1), axis=-1)
    mask = (rank < kept).reshape(L, r, 1, c, 1)
    mask = jnp.broadcast_to(mask, (L, r, block[0], c, block[1]))
    mask = mask.reshape(L, r * block[0], c * block[1])[:, :out, :inp]
    std = (inp * kept / (r * c)) ** -0.5
    vals = std * jax.random.normal(k_val, (L, out, inp), jnp.float32)
    return jnp.where(mask, vals, 0.0)
