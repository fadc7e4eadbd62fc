"""Paper Table 3: inference speedup + model-size reduction from compressed
weights.

Three measurements:
  1. model size: dense vs CSR-compressed bytes (the paper's 5.0MB -> 148KB),
  2. CPU wall-time: dense matmul vs CSR SpMM at the trained sparsity (the
     embedded-CPU proxy for the paper's Mali-T860 numbers),
  3. derived TPU roofline: HBM bytes moved by the dense vs BCSR Pallas
     kernel per forward (the quantity that sets memory-bound inference time
     on the target hardware).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import spc_with_retrain, Timer
from repro.core.metrics import model_size_bytes
from repro.models.cnn import CNN_ZOO
from repro.roofline.analysis import HBM_BW
from repro.sparse.compress import CompressionPlan
from repro.sparse.formats import dense_to_bcsr, dense_to_csr

STEPS = 250


def _csr_matvec_time(w_csr, x, iters=50):
    """numpy CSR SpMM (row-segment reduction) — embedded-CPU style.

    np.add.reduceat quirk: an empty segment [i, i) returns gathered[i]
    instead of 0, so empty rows are zeroed afterwards (and trailing
    indices clamped into range)."""
    data = np.asarray(w_csr.data)
    indices = np.asarray(w_csr.indices)
    indptr = np.asarray(w_csr.indptr)
    assert len(data), "empty CSR (all weights pruned) — lambda too high"
    starts = np.minimum(indptr[:-1], len(data) - 1)
    empty = (indptr[1:] - indptr[:-1]) == 0
    t0 = time.perf_counter()
    for _ in range(iters):
        gathered = x[:, indices] * data          # (B, nnz)
        out = np.add.reduceat(gathered, starts, axis=1)
        out[:, empty] = 0.0
    return (time.perf_counter() - t0) / iters, out


def run(steps: int = STEPS):
    model = CNN_ZOO["lenet5"]
    out = spc_with_retrain(model, lam=1.0, steps=steps, retrain_steps=80)
    params = out["retrain_params"]
    rows = []

    dense_b = model_size_bytes(params, sparse=False)
    sparse_b = model_size_bytes(params, sparse=True)

    # beyond-paper: deep-compression stage (k-means palette + Huffman)
    from benchmarks.common import data_for, evaluate_cnn
    from repro.core.quantize import quantize_tree, quantized_size_bytes
    qparams, qreport = quantize_tree(params, bits=4)
    dc_b = quantized_size_bytes(qparams, bits=4, reports=qreport)
    acc = evaluate_cnn(model, params, data_for(model), n_batches=5)
    qacc = evaluate_cnn(model, qparams, data_for(model), n_batches=5)
    rows.append({"name": "inference_speedup/deep_compression_stage",
                 "us_per_call": 0.0,
                 "derived": (f"csr_kb={sparse_b/1024:.0f},"
                             f"quant4_kb={dc_b/1024:.0f},"
                             f"total_ratio={dense_b/dc_b:.0f}x,"
                             f"acc={acc:.4f},quant_acc={qacc:.4f}")})

    rows.append({"name": "inference_speedup/model_size",
                 "us_per_call": 0.0,
                 "derived": (f"dense_kb={dense_b/1024:.0f},"
                             f"csr_kb={sparse_b/1024:.0f},"
                             f"ratio={dense_b/sparse_b:.1f}x")})

    # fc1 is the dominant layer (400k of 430k weights) — time it
    w = np.asarray(params["fc1"]["w"]).T           # (500, 800)
    x = np.random.default_rng(0).normal(size=(64, 800)).astype(np.float32)
    iters = 50
    t0 = time.perf_counter()
    for _ in range(iters):
        y_dense = x @ w.T
    dense_t = (time.perf_counter() - t0) / iters

    csr = dense_to_csr(w)
    sparse_t, y_sparse = _csr_matvec_time(csr, x, iters)
    np.testing.assert_allclose(y_sparse[:, :w.shape[0]].sum(), y_dense.sum(),
                               rtol=1e-2)
    rows.append({"name": "inference_speedup/fc1_cpu_time",
                 "us_per_call": sparse_t * 1e6,
                 "derived": (f"dense_us={dense_t*1e6:.1f},"
                             f"sparse_us={sparse_t*1e6:.1f},"
                             f"speedup={dense_t/sparse_t:.2f}x,"
                             f"nnz_frac={csr.nnz/w.size:.4f}")})

    # derived TPU memory-bound time: HBM bytes for dense vs BCSR weights
    bcsr = dense_to_bcsr(w, block=CompressionPlan.block)
    dense_bytes = w.size * 4 + x.size * 4
    bcsr_bytes = bcsr.nbytes + x.size * 4
    rows.append({"name": "inference_speedup/tpu_roofline_derived",
                 "us_per_call": bcsr_bytes / HBM_BW * 1e6,
                 "derived": (f"dense_hbm_us={dense_bytes/HBM_BW*1e6:.3f},"
                             f"bcsr_hbm_us={bcsr_bytes/HBM_BW*1e6:.3f},"
                             f"block_density={bcsr.n_blocks/(max(1,(np.prod(bcsr.block_grid)))):.3f}")})

    rows.append(decode_compressed_row())
    return rows


def decode_compressed_row(gen_steps: int = 8):
    """Whole-model dense vs BCSR vs PaletteBCSR decode through the serving
    runtime: the transformer decode loop running on ``CompressedParams``
    (BCSR attention/MLP projections), its 8-bit palette-quantized form
    (Deep Compression stage 2), and the same pruned weights served dense —
    real serving bytes and tokens/s for all three."""
    import jax

    from repro.models.model_zoo import build
    from repro.serve.step import generate
    from repro.sparse.compress import (compress_params,
                                       compressed_size_bytes,
                                       prune_blocks_for_plan,
                                       quantize_compressed)

    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    # (8, 64): the reduced widths would be one (128, 128) block per matrix
    plan = CompressionPlan(block=(8, 64), min_sparsity=0.5)
    pruned = prune_blocks_for_plan(params, plan, 0.85)
    cp = compress_params(pruned, plan)
    qcp = quantize_compressed(cp, bits=8)
    dense_b = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(pruned))
    comp_b = compressed_size_bytes(cp)
    pal_b = compressed_size_bytes(qcp)

    import jax.numpy as jnp

    from repro.serve.step import make_decode_step

    prompt = jnp.zeros((4, 8), jnp.int32)
    # jit once outside the loop: generate() builds fresh jit wrappers per
    # call, so timing it would measure trace+compile, not decode
    prefill = jax.jit(model.prefill)
    decode = jax.jit(make_decode_step(model))

    def run_once(p):
        cache = model.init_cache(prompt.shape[0], prompt.shape[1] + gen_steps)
        logits, cache = prefill(p, prompt, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for t in range(prompt.shape[1], prompt.shape[1] + gen_steps - 1):
            tok, _, cache = decode(p, tok[:, None], cache, jnp.int32(t))
        return tok

    def timed(p):
        jax.block_until_ready(run_once(p))             # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(run_once(p))
        return time.perf_counter() - t0

    dense_t, comp_t, pal_t = timed(pruned), timed(cp), timed(qcp)
    n_tok = prompt.shape[0] * gen_steps
    return {"name": "inference_speedup/decode_dense_vs_compressed",
            "us_per_call": comp_t / n_tok * 1e6,
            "derived": (f"dense_us_tok={dense_t/n_tok*1e6:.1f},"
                        f"compressed_us_tok={comp_t/n_tok*1e6:.1f},"
                        f"palette8_us_tok={pal_t/n_tok*1e6:.1f},"
                        f"dense_tok_s={n_tok/dense_t:.1f},"
                        f"bcsr_tok_s={n_tok/comp_t:.1f},"
                        f"palette8_tok_s={n_tok/pal_t:.1f},"
                        f"dense_kb={dense_b/1024:.0f},"
                        f"bcsr_kb={comp_b/1024:.0f},"
                        f"palette8_kb={pal_b/1024:.0f},"
                        f"size_ratio={dense_b/comp_b:.2f}x,"
                        f"palette_ratio={dense_b/pal_b:.2f}x")}


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=STEPS,
                    help="SpC training steps (CI tier-2 uses a short run)")
    ap.add_argument("--json", default="",
                    help="also write the result rows to this JSON path — "
                         "CI uploads it as the BENCH_pr.json artifact and "
                         "benchmarks/check_regression.py gates the "
                         "compressed-decode tokens/s against the committed "
                         "benchmarks/BENCH_baseline.json")
    args = ap.parse_args()
    rows = run(steps=args.steps)
    for r in rows:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"steps": args.steps, "rows": rows}, f, indent=1)
        print(f"wrote {args.json}")
