"""Pallas BCSR spmm kernel vs pure-jnp oracle: shape/dtype/density sweeps
(interpret mode on CPU; the kernel targets TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bsr_spmm import ops
from repro.kernels.bsr_spmm.ref import gather_block_matmul_ref
from repro.kernels.bsr_spmm.bsr_spmm import gather_block_matmul
from repro.sparse.formats import dense_to_bcsr


def _block_sparse(rng, n, k, block, density):
    br, bc = block
    w = np.zeros((n, k), np.float32)
    for i in range(n // br):
        for j in range(k // bc):
            if rng.random() < density:
                w[i * br:(i + 1) * br, j * bc:(j + 1) * bc] = rng.normal(
                    size=(br, bc))
    return w


@pytest.mark.parametrize("n,k,block", [
    (64, 64, (32, 32)), (96, 160, (32, 32)), (64, 128, (8, 128)),
    (128, 64, (16, 16)),
])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_spmm_fwd_shapes(n, k, block, density):
    rng = np.random.default_rng(hash((n, k, density)) % 2**31)
    w = _block_sparse(rng, n, k, block, density)
    m = dense_to_bcsr(w, block)
    x = jnp.asarray(rng.normal(size=(40, k)), jnp.float32)
    y = ops.spmm(x, m, bm=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ w.T,
                               atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("n,k,block", [(96, 160, (32, 32)), (64, 64, (8, 128))])
def test_spmm_bwd_shapes(n, k, block):
    rng = np.random.default_rng(0)
    w = _block_sparse(rng, n, k, block, 0.4)
    m = dense_to_bcsr(w, block)
    dy = jnp.asarray(rng.normal(size=(24, n)), jnp.float32)
    dx = ops.spmm_t(dy, m, bm=8)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dy) @ w,
                               atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_dtypes(dtype):
    rng = np.random.default_rng(3)
    w = _block_sparse(rng, 64, 64, (32, 32), 0.5).astype(dtype)
    m = dense_to_bcsr(np.asarray(w, np.float32), (32, 32))
    m = jax.tree.map(lambda a: a.astype(dtype)
                     if a.dtype == jnp.float32 else a, m)
    x = jnp.asarray(rng.normal(size=(32, 64)), dtype)
    y = ops.spmm(x, m, bm=32)
    ref = np.asarray(x, np.float32) @ np.asarray(w, np.float32).T
    tol = 1e-4 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(np.asarray(y, np.float32), ref,
                               atol=tol, rtol=tol)


def test_kernel_vs_schedule_oracle():
    """The pallas grid schedule itself vs an index-faithful python oracle."""
    rng = np.random.default_rng(4)
    w = _block_sparse(rng, 64, 96, (32, 32), 0.5)
    m = dense_to_bcsr(w, (32, 32))
    x = jnp.asarray(rng.normal(size=(32, 96)), jnp.float32)
    got = gather_block_matmul(x, m.data, m.gather_idx, m.gather_blk,
                              m.gather_nnz, out_cols=64,
                              transpose_block=True, bm=32, interpret=True)
    want = gather_block_matmul_ref(x, m.data, m.gather_idx, m.gather_blk,
                                   m.gather_nnz, out_cols=64,
                                   transpose_block=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_custom_vjp_matches_dense_grad():
    rng = np.random.default_rng(5)
    w = _block_sparse(rng, 64, 64, (32, 32), 0.6)
    m = dense_to_bcsr(w, (32, 32))
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)

    g_sparse = jax.grad(lambda x_: jnp.sum(jnp.tanh(ops.spmm_ad(x_, m))))(x)
    g_dense = jax.grad(
        lambda x_: jnp.sum(jnp.tanh(x_ @ jnp.asarray(w).T)))(x)
    np.testing.assert_allclose(np.asarray(g_sparse), np.asarray(g_dense),
                               atol=1e-4, rtol=1e-4)


def test_ragged_rows_padded_gather():
    """Rows with very different nnz exercise the padded gather tables."""
    w = np.zeros((96, 96), np.float32)
    w[:32, :] = np.random.default_rng(6).normal(size=(32, 96))  # dense row 0
    w[32:64, :32] = 1.0                                          # 1 block
    # block-row 2 empty
    m = dense_to_bcsr(w, (32, 32))
    assert int(m.gather_nnz[0]) == 3
    assert int(m.gather_nnz[1]) == 1
    assert int(m.gather_nnz[2]) == 0
    x = jnp.asarray(np.eye(96, dtype=np.float32))
    y = ops.spmm(x, m, bm=8)
    np.testing.assert_allclose(np.asarray(y), w.T, atol=1e-5)


@pytest.mark.parametrize("kernel", ["spmm", "spmm_t", "sddmm", "palette"])
def test_compiled_path_rejects_untileable_block(kernel):
    """A block the compiled (Mosaic) kernel cannot tile raises at trace
    time and names the rule — it never falls back to interpret mode."""
    from repro.kernels.bsr_sddmm import ops as sddmm_ops
    from repro.sparse.compress import quantize_bcsr

    rng = np.random.default_rng(5)
    m = dense_to_bcsr(_block_sparse(rng, 64, 128, (8, 128), 0.5), (8, 128))
    x = jnp.ones((16, 128), jnp.float32)
    dy = jnp.ones((16, 64), jnp.float32)
    calls = {"spmm": lambda: ops.spmm(x, m, interpret=False),
             "spmm_t": lambda: ops.spmm_t(dy, m, interpret=False),
             "sddmm": lambda: sddmm_ops.bsr_weight_grad(x, dy, m,
                                                        interpret=False),
             "palette": lambda: ops.spmm_palette(
                 x, quantize_bcsr(m, 8, iters=2), interpret=False)}
    with pytest.raises(ValueError, match=r"multiples of \(8, 128\)"):
        calls[kernel]()
