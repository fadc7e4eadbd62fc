"""Compile rehearsals for the TPU: every Pallas kernel on the serving and
SpC-Retrain paths, compiled by the TPU compiler for a described (not
attached) v5e chip, at smollm-360m's published widths and the default BCSR
block.

Interpret-mode tests cannot see Mosaic's tiling rule (the last two block
dims must be multiples of (8, 128) or whole array dims) or its shape-cast
limits; these compiles can. Nothing runs, so they say nothing about
results or speed. The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.bsr_sddmm import ops as sddmm_ops
from repro.kernels.bsr_spmm import ops as spmm_ops
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.prox_adam import ops as prox_ops
from repro.models.attention import init_paged_kv
from repro.sparse.compress import CompressionPlan
from repro.sparse.formats import PaletteBCSR, dense_to_bcsr

CFG = get_config("smollm-360m")
BLOCK = CompressionPlan.block
M = 256                       # activation rows: 8 slots x a 32-token chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A described-topology compile written to the persistent cache cannot
    be read back without a chip; keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(sharding, fn, *args):
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _mlp_wi(keep=0.1):
    """The (out, in) = (d_ff, d_model) view of an MLP input projection with
    ``keep`` of its blocks resident (pad rows included, as served)."""
    rng = np.random.default_rng(0)
    br, bc = BLOCK
    n, k = CFG.d_ff, CFG.d_model
    occ = rng.random((-(-n // br), -(-k // bc))) < keep
    occ[0, 0] = True
    w = np.kron(occ, np.ones(BLOCK, np.float32))[:n, :k]
    return dense_to_bcsr(w, BLOCK)


def _palette(w, bits):
    n_slots, br, bc = w.data.shape
    codes = np.zeros((n_slots, br, bc // 2 if bits == 4 else bc), np.uint8)
    return PaletteBCSR(
        codes=jnp.asarray(codes), palette=jnp.zeros((2 ** bits,)),
        col_idx=w.col_idx, row_ptr=w.row_ptr, gather_idx=w.gather_idx,
        gather_blk=w.gather_blk, gather_nnz=w.gather_nnz,
        gather_t_idx=w.gather_t_idx, gather_t_blk=w.gather_t_blk,
        gather_t_nnz=w.gather_t_nnz, shape=w.shape, block=w.block,
        n_blocks=w.n_blocks, bits=bits)


@pytest.mark.parametrize("kernel", ["spmm", "spmm_t", "sddmm", "palette8",
                                    "palette4"])
def test_bcsr_kernels_compile_for_v5e(one_chip, no_cache, kernel):
    w = _mlp_wi()
    x = jnp.zeros((M, CFG.d_model), jnp.bfloat16)
    dy = jnp.zeros((M, CFG.d_ff), jnp.bfloat16)
    if kernel == "spmm":
        _compile(one_chip, lambda x, w: spmm_ops.spmm(x, w, interpret=False),
                 x, w)
    elif kernel == "spmm_t":
        _compile(one_chip,
                 lambda d, w: spmm_ops.spmm_t(d, w, interpret=False), dy, w)
    elif kernel == "sddmm":
        _compile(one_chip, lambda x, d, w: sddmm_ops.bsr_weight_grad(
            x, d, w, interpret=False), x, dy, w)
    else:
        pw = _palette(w, int(kernel[-1]))
        _compile(one_chip, lambda x, w: spmm_ops.spmm_palette(
            x, w, interpret=False), x, pw)


@pytest.mark.parametrize("arch,slots,max_seq,chunk", [
    pytest.param("smollm-360m", 8, 544, 1, id="1"),
    pytest.param("smollm-360m", 8, 544, 32, id="32"),
    pytest.param("qwen3-0.6b", 16, 4096, 1, id="qwen3-0.6b-1"),
    pytest.param("qwen3-0.6b", 16, 4096, 256, id="qwen3-0.6b-256"),
])
def test_paged_attention_compiles_for_v5e(one_chip, no_cache, arch, slots,
                                          max_seq, chunk):
    """Decode (C=1) and a prefill chunk over bf16 head-major pools, page
    16: smollm-360m (hd 64, g 3) with 8 slots of 544 tokens (a 512-token
    prompt + 32 new), and the long-context cell's qwen3-0.6b (KV 8, hd
    128, g 2) with 16 slots of 4096 tokens (P = 256) at the decode tick
    and the 256-token prefill chunk. The compile proves the page block
    derived from these shapes fits VMEM at both widths."""
    cfg = get_config(arch)
    ps = 16
    p_log = -(-max_seq // ps)
    pool = jax.eval_shape(
        lambda: init_paged_kv(cfg, slots * p_log + 1, ps, jnp.bfloat16))
    q = jnp.zeros((slots, chunk, cfg.n_heads, cfg.resolved_head_dim),
                  jnp.bfloat16)
    table = jnp.zeros((slots, p_log), jnp.int32)
    positions = jnp.zeros((slots, chunk), jnp.int32)
    _compile(one_chip, lambda q, k, v, t, p: paged_ops.paged_flash_attention(
        q, k, v, t, p, interpret=False), q, pool["k"], pool["v"], table,
        positions)


def test_fused_prox_update_compiles_for_v5e(one_chip, no_cache):
    w = jnp.zeros((CFG.d_model, CFG.d_ff), jnp.float32)
    scalars = jnp.zeros((8,), jnp.float32)
    _compile(one_chip, lambda w, g, m, v, s: prox_ops.fused_update_leaf(
        w, g, m, v, s, interpret=False), w, w, w, w, scalars)
