"""Compiled kernel-bench lane: the Pallas kernel suite vs dense XLA.

Times the four serving-path kernels at serving sparsities and batch shapes:

* paged attention  — fused page-gather flash-decode kernel vs the jnp
                     gather-the-whole-pool reference (decode and mixed
                     prefill/decode tick shapes), with a built-in parity
                     assert (the interpret-mode correctness smoke),
* gather_block_matmul (BCSR spmm) and the palette dequant-matmul vs a
  dense XLA matmul,
* SDDMM (masked weight gradient) vs the dense ``dy.T @ x`` product.

Off-TPU the Pallas numbers are interpret-mode (not meaningful as wall
time; the roofline-derived TPU estimates carry the expected numbers), so
the gateable ``speedup_vs_dense`` field is measured on the path serving
actually takes on this machine (``resolve_backend('auto')``): the jnp ref
kernels on CPU, the compiled Pallas kernels on TPU. It is a same-run
ratio against dense XLA, so it is machine-corrected by construction and
gated by ``benchmarks/check_regression.py`` against
``benchmarks/BENCH_kernels_baseline.json``:

    PYTHONPATH=src python -m benchmarks.kernel_bench --json BENCH_kernels.json
    python -m benchmarks.check_regression BENCH_kernels.json \
        --baseline benchmarks/BENCH_kernels_baseline.json --max-regress 0.5
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import use_interpret
from repro.kernels.bsr_sddmm import ops as sddmm_ops
from repro.kernels.bsr_spmm import ops as spmm_ops
from repro.kernels.bsr_spmm import ref as spmm_ref
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention import ref as paged_ref
from repro.kernels.prox_adam import ops as prox_ops
from repro.roofline.analysis import HBM_BW
from repro.sparse.compress import CompressionPlan, quantize_bcsr
from repro.sparse.formats import dense_to_bcsr

PARITY_TOL = 1e-4


def _time(f, iters=3):
    f()  # compile
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _block_sparse(rng, n, k, bl, sparsity):
    w = np.zeros((n, k), np.float32)
    for i in range(n // bl[0]):
        for j in range(k // bl[1]):
            if rng.random() >= sparsity:
                w[i*bl[0]:(i+1)*bl[0], j*bl[1]:(j+1)*bl[1]] = \
                    rng.normal(size=bl)
    return w


# -- paged attention --------------------------------------------------------

def _paged_scenario(rng, b, c, ctx):
    kv, g, hd, ps = 4, 4, 64, 16
    h = kv * g
    p_log = -(-(ctx + c) // ps)
    n_pages = 1 + b * p_log
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, kv, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, kv, ps, hd)), jnp.float32)
    table = jnp.asarray(
        1 + np.arange(b * p_log, dtype=np.int32).reshape(b, p_log))
    start = np.full(b, ctx, np.int32)
    positions = jnp.asarray(start[:, None] + np.arange(c)[None], jnp.int32)
    return q, kp, vp, table, positions, (kv, hd, ps, p_log)


def _paged_row(name, rng, b, c, ctx, iters):
    q, kp, vp, table, positions, (kv, hd, ps, p_log) = \
        _paged_scenario(rng, b, c, ctx)
    ref_fn = jax.jit(functools.partial(paged_ref.paged_attention_ref,
                                       window=None))
    ref_us = _time(lambda: ref_fn(q, kp, vp, table, positions), iters)
    pal_us = _time(lambda: paged_ops.paged_flash_attention(
        q, kp, vp, table, positions), iters)
    err = float(jnp.max(jnp.abs(
        paged_ops.paged_flash_attention(q, kp, vp, table, positions)
        - ref_fn(q, kp, vp, table, positions))))
    if err > PARITY_TOL:
        raise SystemExit(f"{name}: paged-attention kernel diverges from the "
                         f"jnp reference (max_err={err:.2e} > {PARITY_TOL})")
    # TPU roofline: the gather path reads (and writes a copy of) the whole
    # (B, P*ps) context per layer call; the paged kernel reads only the
    # pages below the causal frontier
    kv_bytes = 2 * 4 * kv * hd * ps                      # k+v, one page
    gather_b = 3 * b * p_log * kv_bytes                  # read + copy out
    live = -(-(ctx + c) // ps)
    paged_b = b * live * kv_bytes
    derived = (f"max_err={err:.1e},ref_us={ref_us:.1f},"
               f"tpu_gather_us={gather_b/HBM_BW*1e6:.3f},"
               f"tpu_paged_us={paged_b/HBM_BW*1e6:.3f}")
    if not use_interpret():                              # compiled kernel
        derived += f",speedup_vs_dense={ref_us/max(pal_us, 1e-9):.4f}"
    return {"name": name, "us_per_call": pal_us, "derived": derived}


# -- BCSR / palette spmm ----------------------------------------------------

def _spmm_row(name, rng, sparsity, iters, bits=0):
    m_rows, n, k, bl = 64, 512, 512, CompressionPlan.block
    w = _block_sparse(rng, n, k, bl, sparsity)
    mat = dense_to_bcsr(w, bl)
    x = jnp.asarray(rng.normal(size=(m_rows, k)), jnp.float32)
    wd = jnp.asarray(w)
    dense_fn = jax.jit(lambda a: a @ wd.T)
    dense_us = _time(lambda: dense_fn(x), iters)
    if bits:
        mat = quantize_bcsr(mat, bits)
        ref_fn = jax.jit(spmm_ref.spmm_palette_fwd_ref)
        pal_us = _time(lambda: spmm_ops.spmm_palette(x, mat, bm=64), iters)
    else:
        ref_fn = jax.jit(spmm_ref.spmm_fwd_ref)
        pal_us = _time(lambda: spmm_ops.spmm(x, mat, bm=64), iters)
    ref_us = _time(lambda: ref_fn(x, mat), iters)
    serving_us = pal_us if not use_interpret() else ref_us
    density = mat.n_blocks / ((n // bl[0]) * (k // bl[1]))
    dense_b = (w.size + x.size + m_rows * n) * 4
    bcsr_b = mat.nbytes + (x.size + m_rows * n) * 4
    return {"name": name, "us_per_call": pal_us,
            "derived": (f"density={density:.2f},dense_us={dense_us:.1f},"
                        f"ref_us={ref_us:.1f},"
                        f"tpu_dense_us={dense_b/HBM_BW*1e6:.3f},"
                        f"tpu_bcsr_us={bcsr_b/HBM_BW*1e6:.3f},"
                        f"speedup_vs_dense="
                        f"{dense_us/max(serving_us, 1e-9):.4f}")}


def _sddmm_row(name, rng, sparsity, iters):
    m_rows, n, k, bl = 64, 512, 512, CompressionPlan.block
    w = _block_sparse(rng, n, k, bl, sparsity)
    mat = dense_to_bcsr(w, bl)
    x = jnp.asarray(rng.normal(size=(m_rows, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m_rows, n)), jnp.float32)
    dense_fn = jax.jit(lambda a, b: a.T @ b)
    dense_us = _time(lambda: dense_fn(dy, x), iters)
    pal_us = _time(lambda: sddmm_ops.bsr_weight_grad(x, dy, mat, bm=64),
                   iters)
    # parity smoke: the kernel (the path every backend's dw takes; see
    # sparse/ops.py) vs the eager per-slot reference, one shot
    err = float(jnp.max(jnp.abs(
        sddmm_ops.bsr_weight_grad(x, dy, mat, bm=64)
        - sddmm_ops.bsr_weight_grad_ref(x, dy, mat))))
    if err > PARITY_TOL:
        raise SystemExit(f"{name}: SDDMM kernel diverges from reference "
                         f"(max_err={err:.2e} > {PARITY_TOL})")
    dense_b = (w.size + x.size + dy.size) * 4
    bcsr_b = mat.data.size * 4 + (x.size + dy.size) * 4
    return {"name": name, "us_per_call": pal_us,
            "derived": (f"max_err={err:.1e},dense_us={dense_us:.1f},"
                        f"tpu_dense_us={dense_b/HBM_BW*1e6:.3f},"
                        f"tpu_sddmm_us={bcsr_b/HBM_BW*1e6:.3f},"
                        f"speedup_vs_dense="
                        f"{dense_us/max(pal_us, 1e-9):.4f}")}


def run(iters: int = 3):
    rows = []
    rng = np.random.default_rng(0)

    # paged attention: a pure-decode tick and a mixed prefill tick at the
    # engine's default-ish shapes (B slots x C new tokens, ctx tokens deep)
    rows.append(_paged_row("kernel/paged_attention_decode", rng,
                           b=4, c=1, ctx=96, iters=iters))
    rows.append(_paged_row("kernel/paged_attention_mixed_prefill", rng,
                           b=4, c=32, ctx=64, iters=iters))

    # BCSR spmm + palette dequant-matmul at serving sparsities
    rows.append(_spmm_row("kernel/bsr_spmm_s85", rng, 0.85, iters))
    rows.append(_spmm_row("kernel/bsr_spmm_s95", rng, 0.95, iters))
    rows.append(_spmm_row("kernel/palette8_spmm_s90", rng, 0.90, iters,
                          bits=8))

    # SDDMM masked weight gradient vs dense dy.T @ x
    rows.append(_sddmm_row("kernel/sddmm_dw_s90", rng, 0.90, iters))

    # fused prox-adam: 1 HBM pass per tensor vs ~7 unfused
    shape = (1024, 512)
    wt = jnp.asarray(rng.normal(size=shape), jnp.float32)
    g = jnp.asarray(rng.normal(size=shape), jnp.float32)
    mm_ = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    sc = prox_ops.make_scalars(1e-3, 1.0, 0.9, 0.999, 1e-8, 1)
    us = _time(lambda: prox_ops.fused_update_leaf(wt, g, mm_, v, sc), iters)
    nbytes = wt.nbytes
    fused = 7 * nbytes        # r/w of w,m,v + read g
    unfused = 16 * nbytes     # each sub-op round-trips HBM
    rows.append({"name": "kernel/fused_prox_adam_interp",
                 "us_per_call": us,
                 "derived": (f"tpu_fused_us={fused/HBM_BW*1e6:.3f},"
                             f"tpu_unfused_us={unfused/HBM_BW*1e6:.3f},"
                             f"fusion_win={unfused/fused:.2f}x")})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="", help="write rows to this path")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)
    rows = run(iters=args.iters)
    for r in rows:
        print(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
