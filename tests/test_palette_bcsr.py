"""Quantized BCSR serving (PaletteBCSR — Deep Compression stage 2).

Covers the acceptance criteria of the quantized-serving tentpole:
  * uint4 nibble packing round-trips bit-exactly,
  * ``quantize_bcsr`` preserves the sparsity pattern (code 0 == exact zero)
    and shares the BlockCSR index/gather tables by reference,
  * the palette kernel (fused dequant) matches the ref backend and the
    dequantize-then-BCSR oracle exactly, at 8 and 4 bits,
  * PaletteBCSR serving logits match the BCSR path: bit-exactly against the
    dequantized model, and within tolerance against the fp model at 8-bit,
  * real bytes: palette-quantized sparse store <= 1/3 of the fp32 BlockCSR
    store at realistic layer sizes (8-bit), <= 1/6 at 4-bit,
  * Checkpointer round-trips PaletteBCSR without densifying (codes stay
    packed on disk) and ``restore_compressed`` rebuilds the quantized plan,
  * the train --sparse --quantize-bits -> serve --ckpt-dir CLI loop serves
    from PaletteBCSR,
  * quantized weights are rejected by the retraining paths (serving-only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.models.model_zoo import build
from repro.sparse import ops as sparse_ops
from repro.sparse.compress import (CompressionPlan, bcsr_equiv_size_bytes,
                                   compress_params, compressed_size_bytes,
                                   dequantize_compressed, iter_bcsr,
                                   prune_blocks_for_plan, quantize_bcsr,
                                   quantize_compressed, split_trainable)
from repro.sparse.formats import (BlockCSR, PaletteBCSR, dense_to_bcsr,
                                  pack_uint4, unpack_uint4)

PLAN = CompressionPlan(block=(8, 64), min_sparsity=0.3, min_size=4096)


def _block_sparse(shape=(512, 1024), block=(8, 64), keep=0.25, seed=0):
    """Random dense matrix with whole (br, bc) blocks zeroed."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=shape).astype(np.float32)
    br, bc = block
    occ = rng.random((shape[0] // br, shape[1] // bc)) < keep
    mask = np.kron(occ, np.ones((br, bc), bool))
    return w * mask


# ---------------------------------------------------------------------------
# Packing + format construction
# ---------------------------------------------------------------------------

def test_uint4_pack_roundtrip():
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 16, size=(5, 8, 64)).astype(np.uint8)
    packed = pack_uint4(jnp.asarray(codes))
    assert packed.shape == (5, 8, 32) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_uint4(packed)), codes)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_bcsr_preserves_pattern_and_indices(bits):
    m = dense_to_bcsr(_block_sparse(), block=(8, 64))
    q = quantize_bcsr(m, bits)
    assert isinstance(q, PaletteBCSR) and q.bits == bits
    # index/gather tables shared by reference — no extra copies
    assert q.col_idx is m.col_idx and q.gather_idx is m.gather_idx
    assert float(q.palette[0]) == 0.0
    # code 0 <-> exact zero: the sparsity pattern survives quantization
    deq = np.asarray(q.dequantize().data)
    orig = np.asarray(m.data)
    np.testing.assert_array_equal(deq == 0, orig == 0)


def test_quantize_bcsr_exact_on_small_palette():
    """Weights drawn from a small, well-separated value set are represented
    exactly at 8-bit (k-means converges onto the values)."""
    rng = np.random.default_rng(1)
    levels = np.linspace(-1.0, 1.0, 9).astype(np.float32)
    w = levels[rng.integers(0, 9, size=(256, 512))]
    w[np.kron(rng.random((32, 8)) < 0.7,
              np.ones((8, 64), bool))] = 0.0
    m = dense_to_bcsr(w, block=(8, 64))
    q = quantize_bcsr(m, 8)
    np.testing.assert_allclose(np.asarray(q.dequantize().data),
                               np.asarray(m.data), atol=1e-6)


def test_quantize_bcsr_stacked_per_slice_palettes():
    ws = [_block_sparse(seed=s) for s in range(3)]
    ms = [dense_to_bcsr(w, block=(8, 64)) for w in ws]
    from repro.sparse.formats import pad_bcsr
    n_slots = max(m.data.shape[0] for m in ms)
    jmax = max(m.gather_idx.shape[1] for m in ms)
    jmax_t = max(m.gather_t_idx.shape[1] for m in ms)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[pad_bcsr(m, n_slots, jmax, jmax_t) for m in ms])
    q = quantize_bcsr(stacked, 8)
    assert q.codes.ndim == 4 and q.palette.shape == (3, 256)
    deq = np.asarray(q.dequantize().data)
    for i, m in enumerate(ms):
        d = np.asarray(pad_bcsr(m, n_slots, jmax, jmax_t).data)
        np.testing.assert_array_equal((deq[i] == 0), (d == 0))


def test_quantize_bcsr_all_zero_slice():
    """A fully pruned (empty) BCSR quantizes to all-zero codes/palette."""
    m = dense_to_bcsr(np.zeros((64, 128), np.float32), block=(8, 64))
    q = quantize_bcsr(m, 8)
    assert np.all(np.asarray(q.codes) == 0)
    assert np.all(np.asarray(q.palette) == 0)
    np.testing.assert_array_equal(np.asarray(q.to_dense()),
                                  np.zeros((64, 128)))


# ---------------------------------------------------------------------------
# Kernel paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_palette_spmm_backend_symmetry(bits):
    w = _block_sparse(shape=(128, 256))
    q = quantize_bcsr(dense_to_bcsr(w, block=(8, 64)), bits)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(16, 256)),
                    jnp.float32)
    y_ref = sparse_ops.sparse_matmul(x, q, backend="ref")
    y_pal = sparse_ops.sparse_matmul(x, q, backend="pallas")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               atol=1e-5, rtol=1e-5)
    # and both equal the dequantize-then-fp-BCSR oracle
    y_deq = sparse_ops.sparse_matmul(x, q.dequantize(), backend="ref")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_deq),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_palette_x_gradient_defined_on_both_backends(backend):
    """dx must exist (and agree with the dequantized-BCSR product) on both
    backends — serving code that differentiates through logits (saliency,
    grad-through-generate) must not diverge between CPU tests and TPU."""
    w = _block_sparse(shape=(128, 256))
    q = quantize_bcsr(dense_to_bcsr(w, block=(8, 64)), 8)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(8, 256)),
                    jnp.float32)

    def loss(xx):
        return jnp.sum(sparse_ops.sparse_matmul(xx, q, backend=backend) ** 2)

    g = jax.jit(jax.grad(loss))(x)
    y = sparse_ops.sparse_matmul(x, q.dequantize(), backend="ref")
    g_ref = np.asarray(
        sparse_ops.sparse_matmul_t(2.0 * y, q, backend="ref"))
    np.testing.assert_allclose(np.asarray(g), g_ref, atol=1e-3, rtol=1e-4)


def test_sparse_matmul_t_accepts_palette():
    w = _block_sparse(shape=(128, 256))
    m = dense_to_bcsr(w, block=(8, 64))
    q = quantize_bcsr(m, 8)
    dy = jnp.asarray(np.random.default_rng(4).normal(size=(8, 128)),
                     jnp.float32)
    out_q = sparse_ops.sparse_matmul_t(dy, q, backend="ref")
    out_d = sparse_ops.sparse_matmul_t(dy, q.dequantize(), backend="ref")
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_d),
                               atol=1e-6)


def test_palette_bytes_ratio():
    """The tentpole size criterion at realistic layer sizes: 8-bit palette
    store <= 1/3 of the fp32 BlockCSR store, 4-bit <= 1/6."""
    m = dense_to_bcsr(_block_sparse(shape=(1024, 1024)), block=(8, 64))
    q8, q4 = quantize_bcsr(m, 8), quantize_bcsr(m, 4)
    assert q8.bcsr_equiv_nbytes == m.nbytes
    assert 3 * q8.nbytes <= m.nbytes
    assert 6 * q4.nbytes <= m.nbytes


# ---------------------------------------------------------------------------
# Whole-model serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def quantized_setup():
    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    pruned = prune_blocks_for_plan(params, PLAN, 0.75)
    cp = compress_params(pruned, PLAN)
    qcp = quantize_compressed(cp, bits=8)
    return model, cp, qcp


def test_quantize_compressed_plan_and_leaves(quantized_setup):
    _, cp, qcp = quantized_setup
    assert qcp.plan.quantize_bits == 8
    kinds = {type(m).__name__ for _, m in iter_bcsr(qcp)}
    assert kinds == {"PaletteBCSR"}
    # bytes: quantized total strictly below fp BCSR total, and the fp
    # equivalent accounting reproduces the unquantized total
    assert compressed_size_bytes(qcp) < compressed_size_bytes(cp)
    assert bcsr_equiv_size_bytes(qcp) == compressed_size_bytes(cp)


def test_palette_serve_matches_dequantized_bitexact(quantized_setup):
    """Serving from PaletteBCSR == serving the dequantized BCSR model: the
    fused-dequant kernel path introduces no error of its own."""
    model, _, qcp = quantized_setup
    dcp = dequantize_compressed(qcp)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                model.cfg.vocab)
    cache_q = model.init_cache(2, 16)
    cache_d = model.init_cache(2, 16)
    lq, cache_q = jax.jit(model.prefill)(qcp, prompt, cache_q)
    ld, cache_d = jax.jit(model.prefill)(dcp, prompt, cache_d)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               atol=1e-5, rtol=1e-5)
    tok = jnp.argmax(lq, -1)[:, None].astype(jnp.int32)
    step = jax.jit(model.decode_step)
    lq2, _ = step(qcp, tok, cache_q, jnp.int32(8))
    ld2, _ = step(dcp, tok, cache_d, jnp.int32(8))
    np.testing.assert_allclose(np.asarray(lq2), np.asarray(ld2),
                               atol=1e-5, rtol=1e-5)


def test_palette_serve_near_fp_bcsr_at_8bit(quantized_setup):
    """8-bit logits-parity tolerance vs the unquantized BCSR path (255
    clusters per layer keep distortion small end-to-end)."""
    model, cp, qcp = quantized_setup
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                model.cfg.vocab)
    lb, _ = jax.jit(model.prefill)(cp, prompt, model.init_cache(2, 16))
    lq, _ = jax.jit(model.prefill)(qcp, prompt, model.init_cache(2, 16))
    err = float(jnp.abs(lq - lb).max())
    scale = float(jnp.abs(lb).max())
    assert err <= 0.05 * max(scale, 1.0), (err, scale)


def test_palette_checkpoint_roundtrip(tmp_path, quantized_setup):
    _, _, qcp = quantized_setup
    ckpt = Checkpointer(str(tmp_path), keep_n=2)
    ckpt.save(3, qcp)
    fmts = {e["format"] for e in ckpt.manifest(3)["leaves"]}
    assert "palette_bcsr" in fmts and "bcsr" not in fmts
    back = ckpt.restore(3, like=qcp)
    flat_a, tda = jax.tree_util.tree_flatten(qcp)
    flat_b, tdb = jax.tree_util.tree_flatten(back)
    assert tda == tdb                         # bits/metas included
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # template-free restore rebuilds PaletteBCSR leaves (no densification)
    back2 = ckpt.restore_compressed(3)
    kinds = {type(m).__name__ for _, m in iter_bcsr(back2)}
    assert kinds == {"PaletteBCSR"}
    m0 = next(m for _, m in iter_bcsr(back2))
    assert m0.bits == 8 and m0.codes.dtype == jnp.uint8


def test_palette4_checkpoint_restores_interleaved_nibbles(tmp_path):
    """A 4-bit checkpoint written with the older interleaved nibble packing
    (no "nibbles" marker in its manifest entry) restores to the same codes
    as one written with the split packing; an unknown marker is refused."""
    import json
    import os

    q = quantize_bcsr(dense_to_bcsr(_block_sparse(shape=(64, 256)),
                                    block=(8, 64)), 4)
    ckpt = Checkpointer(str(tmp_path))
    path = ckpt.save(1, {"w": q})
    assert ckpt.manifest(1)["leaves"][0]["nibbles"] == "split"

    codes = np.asarray(unpack_uint4(q.codes))
    arrays = dict(np.load(os.path.join(path, "arrays.npz")))
    arrays["w__codes"] = (codes[..., 0::2] | (codes[..., 1::2] << 4)
                          ).astype(np.uint8)
    np.savez(os.path.join(path, "arrays.npz"), **arrays)
    manifest = ckpt.manifest(1)
    del manifest["leaves"][0]["nibbles"]
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    back = ckpt.restore(1, like={"w": q})["w"]
    np.testing.assert_array_equal(np.asarray(back.codes), np.asarray(q.codes))
    np.testing.assert_array_equal(np.asarray(back.to_dense()),
                                  np.asarray(q.to_dense()))

    manifest["leaves"][0]["nibbles"] = "zigzag"
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="nibble packing"):
        ckpt.restore(1, like={"w": q})


def test_quantized_is_serving_only(quantized_setup):
    _, _, qcp = quantized_setup
    with pytest.raises(TypeError, match="serving-only"):
        split_trainable(qcp)
    from repro.kernels.bsr_sddmm import ops as sddmm_kops
    m = next(m for _, m in iter_bcsr(qcp))
    with pytest.raises(TypeError, match="not .*trainable|PaletteBCSR"):
        sddmm_kops.bsr_weight_grad(jnp.zeros((8, m.shape[1])),
                                   jnp.zeros((8, m.shape[0])), m)


# ---------------------------------------------------------------------------
# End-to-end CLI: train --sparse --quantize-bits 8 -> serve --ckpt-dir
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_train_quantized_to_serve(tmp_path, capsys):
    from repro.launch import serve as serve_launch
    from repro.launch import train as train_launch

    cp, _, _, report = train_launch.main(
        ["--arch", "smollm-360m", "--reduced", "--sparse",
         "--quantize-bits", "8", "--steps", "12", "--debias-steps", "3",
         "--batch", "2", "--seq", "16", "--lr", "3e-3",
         "--compress", "group_l1:100", "--block", "8", "64",
         "--ckpt-dir", str(tmp_path), "--log-every", "4"])
    kinds = {type(m).__name__ for _, m in iter_bcsr(cp)}
    assert kinds == {"PaletteBCSR"}, "checkpointed model is not quantized"
    assert report["palette_bytes"] < report["bcsr_bytes"]

    out = serve_launch.main(
        ["--arch", "smollm-360m", "--reduced", "--sparse",
         "--ckpt-dir", str(tmp_path), "--batch", "2",
         "--prompt-len", "4", "--gen", "4"])
    assert out.shape == (2, 4)
    printed = capsys.readouterr().out
    assert "pal8" in printed, "serve did not report the palette format"
    assert "palette=" in printed and "bcsr=" in printed
