"""Pallas TPU flash-attention forward kernel (beyond-paper optimization).

Why it exists here: the dry-run roofline shows every attention architecture
memory-bound — the streaming-softmax in jnp keeps (q_chunk, kv_chunk) score
tiles crossing HBM ~4x per chunk pair, so attention-interior traffic scales
with S^2. This kernel keeps the score tile, running max/sum, and the output
accumulator in VMEM: HBM traffic collapses to the q/k/v/o kernel I/O (2/S of
the interior traffic; EXPERIMENTS.md §Perf iteration A3/K1).

Schedule: grid (B*H, nq, nkv), kv innermost; VMEM scratch carries
(m, l, acc) across kv blocks; causal block-skip via @pl.when (also halves
the FLOPs vs the masked-dense jnp path). GQA is handled in the k/v
BlockSpec index maps (kv head = q head // group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref,
            m_sc, l_sc, acc_sc,
            *, scale, causal, window, bq, bk, nkv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # causal block skip: kv block strictly above the diagonal contributes
    # nothing -> skip its matmuls entirely (FLOPs saved on real hardware)
    run = jnp.bool_(True)
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=1)
        acc_sc[...] = corr[:, None] * acc_sc[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=None,
                        bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """q: (BH, S, hd); k, v: (BKV, S, hd) with BH = BKV * group.

    Returns o: (BH, S, hd)."""
    bh, s, hd = q.shape
    bkv = k.shape[0]
    g = bh // bkv
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nkv = s // bq, s // bk
    scale = hd ** -0.5

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bk=bk, nkv=nkv)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
