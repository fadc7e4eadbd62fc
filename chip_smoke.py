#!/usr/bin/env python3
"""Prove that the system runs, compiled, on a TPU chip through its entry
points, at smollm-360m's full published widths (32 layers, random weights
from a fixed seed).

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded path on four chips

One chip runs these phases, in one process:

* device  — JAX's first device is a TPU, and Pallas kernels run compiled
            (no interpret mode).
* kernels — each Pallas kernel at smollm-360m widths against its jnp
            reference: BCSR matmul forward and transposed, SDDMM weight
            gradient, palette8/palette4 matmul, paged attention.
* serve   — ``launch/serve`` main: ``--sparse`` prune path at 0.9 block
            sparsity, ``--engine``, ``--attn-backend auto``, 8 greedy
            requests with prompts of 16..512 tokens and 32 new tokens each.
            Every request completes with its token count; the engine step
            holds the BCSR matmul and paged-attention kernels; one prefill's
            logits match the densified weights run through plain XLA.
* train   — ``launch/train`` main: ``--sparse`` SpC steps, compression,
            debias steps on the compressed params (BCSR matmul, its
            transpose and SDDMM, compiled), a compressed checkpoint that
            restores bit-exactly and serves through the engine.

``--four-chips`` runs only the sharded compressed prefill (``--mesh 2,2``)
against the one-device prefill, and reports how the compiled program
partitions the kernels.

Each phase prints its wall time, compile time and the device's peak memory.
The last line of standard output is a JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
ARCH = "smollm-360m"

# Relative L2 error allowed between a compiled kernel and its f32 jnp
# reference. Both run on the chip; the reference's f32 matmuls run at
# "highest" precision and the kernels accumulate in f32 over bf16 or f32
# operands, so a correct kernel lands near bf16 rounding (2**-9 ~ 2e-3 per
# operand) and a wrong one near 1.
KERNEL_RTOL = 1e-2

# Relative L2 error allowed between the served prefill logits (BCSR and
# paged-attention kernels, bf16 activations) and the densified weights run
# through plain XLA. On a v5e the sound model reads 5.14e-3 (bf16 roundings
# in a different order on the two paths). Zeroing the largest (128, 128)
# block of one attention projection in one layer reads 1.02e-2 (wq, first
# layer), 9.06e-2 (wv, last layer) and 0.85 (wo, first layer); the limit
# sits between the sound reading and the smallest of these. A zeroed MLP
# block is invisible here: at random init the MLP adds under 1% to the
# residual stream, below bf16 resolution for one block, so the BCSR matmul
# itself is checked by the kernels phase.
LOGITS_RTOL = 7e-3


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(np.all(np.isfinite(got)), "non-finite values in the result")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.secs, self.hits, self.misses


def run_phase(name, fn, stats, device):
    t0 = time.perf_counter()
    c0 = stats.snapshot()
    try:
        info = fn() or {}
    except SmokeFailure as e:
        raise SmokeFailure(f"phase {name}: {e}") from e
    except (Exception, SystemExit) as e:    # launchers exit via SystemExit
        traceback.print_exc()
        raise SmokeFailure(f"phase {name}: {type(e).__name__}: {e}") from e
    c1 = stats.snapshot()
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use")
    row = {"phase": name, "ok": True,
           "wall_s": time.perf_counter() - t0,
           "compile_s": c1[0] - c0[0],
           "cache_hits": c1[1] - c0[1], "cache_misses": c1[2] - c0[2],
           "peak_bytes_in_use": mem, **info}
    print(f"[chip_smoke] {json.dumps(row)}", flush=True)


# -- phases -----------------------------------------------------------------

def phase_device(expect_chips: int):
    import jax

    from repro.kernels import use_interpret

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"JAX's first device is on platform {d.platform!r} "
          f"({d.device_kind}), not a TPU")
    check(len(devs) >= expect_chips,
          f"{expect_chips} chips needed, JAX sees {len(devs)}")
    check(not use_interpret(),
          "Pallas kernels would run in interpret mode (REPRO_PALLAS_INTERPRET="
          f"{os.environ.get('REPRO_PALLAS_INTERPRET')!r})")
    return {"kind": d.device_kind, "count": len(devs)}


def phase_kernels():
    """Each compiled kernel against its jnp reference at smollm-360m's
    widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.bsr_sddmm import ops as sddmm_ops
    from repro.kernels.bsr_spmm import ops as spmm_ops
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.sparse.compress import CompressionPlan, quantize_bcsr
    from repro.sparse.formats import dense_to_bcsr

    cfg = get_config(ARCH)
    d_model, d_ff, n_heads = cfg.d_model, cfg.d_ff, cfg.n_heads
    n_kv, head_dim = cfg.n_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    block = CompressionPlan.block
    occ = rng.random((-(-d_ff // block[0]), -(-d_model // block[1]))) < 0.1
    occ[0, 0] = True
    w = (np.kron(occ, np.ones(block)) * rng.normal(
        size=(occ.shape[0] * block[0], occ.shape[1] * block[1])))
    w = dense_to_bcsr(w[:d_ff, :d_model].astype(np.float32), block)
    x = jnp.asarray(rng.normal(size=(256, d_model)), jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(256, d_ff)), jnp.bfloat16)
    b, ps, p_log = 8, 16, 34
    perm = rng.permutation(np.arange(1, b * p_log + 1)).astype(np.int32)
    table = jnp.asarray(perm.reshape(b, p_log))
    shape = (b * p_log + 1, n_kv, ps, head_dim)
    kp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    cases = {"spmm": (spmm_ops.spmm, spmm_ops.spmm_fwd_ref, (x, w)),
             "spmm_t": (spmm_ops.spmm_t, spmm_ops.spmm_bwd_ref, (dy, w)),
             "sddmm": (sddmm_ops.bsr_weight_grad,
                       sddmm_ops.bsr_weight_grad_ref, (x, dy, w))}
    for bits in (8, 4):
        cases[f"palette{bits}"] = (spmm_ops.spmm_palette,
                                   spmm_ops.spmm_palette_fwd_ref,
                                   (x, quantize_bcsr(w, bits)))
    for c, splits in ((32, 1), (1, 4)):
        q = jnp.asarray(rng.normal(size=(b, c, n_heads, head_dim)),
                        jnp.bfloat16)
        start = rng.integers(0, p_log * ps - c, size=b)
        pos = jnp.asarray(start[:, None] + np.arange(c)[None], jnp.int32)
        cases[f"paged_c{c}"] = (
            functools.partial(paged_ops.paged_flash_attention,
                              kv_splits=splits),
            paged_ops.paged_attention_ref, (q, kp, vp, table, pos))
    errs = {}
    for name, (kernel, ref, args) in cases.items():
        got = kernel(*args)
        with jax.default_matmul_precision("highest"):
            want = ref(*args)
        errs[name] = rel_err(got, want)
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_RTOL}
    check(not bad, f"kernel rel. L2 error above {KERNEL_RTOL}: {bad}")
    return {"rel_err": errs, "rtol": KERNEL_RTOL}


def _write_requests(path, prompt_lens, gen):
    with open(path, "w") as f:
        json.dump([{"prompt_len": n, "gen": gen} for n in prompt_lens], f)


def _check_completions(out, prompt_lens, gen):
    comps = out["completions"]
    check(len(comps) == len(prompt_lens),
          f"{len(comps)} of {len(prompt_lens)} requests completed")
    for c in comps:
        check(len(c.tokens) == gen,
              f"request {c.request_id} returned {len(c.tokens)} tokens, "
              f"not {gen}")
    got = sorted(c.n_prompt for c in comps)
    check(got == sorted(prompt_lens), f"prompt lengths {got}")


PROMPT_LENS = (16, 40, 77, 128, 200, 311, 400, 512)
GEN = 32


def phase_serve(tmp):
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.kernels.bsr_spmm.bsr_spmm import NAME as SPMM
    from repro.kernels.paged_attention.paged_attention import NAME as PAGED
    from repro.serve.paged_kv import init_paged_cache
    from repro.sparse.compress import densify_compressed

    reqs = os.path.join(tmp, "requests.json")
    _write_requests(reqs, PROMPT_LENS, GEN)
    out = serve.main(["--arch", ARCH, "--sparse", "--sparsity", "0.9",
                      "--engine", "--attn-backend", "auto",
                      "--requests", reqs, "--max-batch", "8",
                      "--prefill-chunk", "32", "--page-size", "16"])
    _check_completions(out, PROMPT_LENS, GEN)
    engine = out["engine"]
    for width in sorted(engine.tick_widths):
        text = engine.lower_step(width).as_text()
        for kernel in (SPMM, PAGED):
            check(f'kernel_name = "{kernel}"' in text,
                  f"the width-{width} engine step holds no {kernel} "
                  "tpu_custom_call")

    # one prefill: the engine's compressed step (BCSR + paged attention
    # kernels) against the densified weights through plain XLA
    model, cp = engine.model, engine.params
    n = PROMPT_LENS[-1]
    ps = engine.config.page_size
    p_log = -(-n // ps)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, n), 0,
                                model.cfg.vocab)
    pools = init_paged_cache(model, p_log + 1, ps)
    table = jax.numpy.arange(1, p_log + 1, dtype=jax.numpy.int32)[None]
    zero = jax.numpy.zeros((1,), jax.numpy.int32)
    step = jax.jit(lambda p, t, pl, tb, s, k: model.paged_step(
        p, t, pl, tb, s, k, backend="auto"))
    got, _ = step(cp, tokens, pools, table, zero, zero + n)
    dense = densify_compressed(cp, model.init(jax.random.PRNGKey(0)))
    want, _ = jax.jit(model.prefill)(dense, tokens, model.init_cache(1, n))
    err = rel_err(got, want)
    check(err <= LOGITS_RTOL,
          f"prefill logits rel. L2 error {err:.4g} > {LOGITS_RTOL}")
    s = out["stats"]
    return {"requests": len(out["completions"]),
            "generated": s["n_generated"], "ticks": s["n_ticks"],
            "tick_widths": sorted(engine.tick_widths),
            "logits_rel_err": err, "logits_rtol": LOGITS_RTOL,
            "logits_top1_match": bool(np.argmax(np.asarray(got))
                                      == np.argmax(np.asarray(want)))}


# SpC-Retrain for a few steps. The prox shrinks every (128, 128) block's
# norm by lr * lambda per step. At init (He, truncated normal) the gated
# MLP input projections' full blocks have norm ~5.1 and their half-width
# edge blocks (d_model 960 = 7.5 blocks) ~3.6, so 4 steps at
# lr * lambda = 1.1 zero the edge blocks and keep the rest: those layers
# compress at 1/8 block sparsity and their debias steps run the BCSR
# matmul, its transpose and the SDDMM kernel.
TRAIN_FLAGS = ("--steps", "4", "--debias-steps", "2", "--batch", "2",
               "--seq", "64", "--lr", "1e-3", "--compress", "group_l1:1100",
               "--min-block-sparsity", "0.1", "--log-every", "1")


def phase_train(tmp):
    import jax
    import numpy as np

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch import serve, train
    from repro.sparse.compress import iter_bcsr

    ckpt = os.path.join(tmp, "spc")
    cp, hist_spc, hist_db, report = train.main(
        ["--arch", ARCH, "--sparse", *TRAIN_FLAGS, "--ckpt-dir", ckpt])
    losses = [h["loss"] for h in hist_spc + hist_db]
    check(hist_spc and hist_db, "no SpC or debias steps were logged")
    check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    blocks = {name: m.n_blocks for name, m in iter_bcsr(cp)}
    check(any(blocks.values()),
          "no compressed layer kept a block: debias ran no BCSR kernel")

    restored = Checkpointer(os.path.join(ckpt, "compressed")) \
        .restore_compressed()
    a, b = jax.tree.leaves(cp), jax.tree.leaves(restored)
    check(len(a) == len(b) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b)),
        "the compressed checkpoint does not restore bit-exactly")

    reqs = os.path.join(tmp, "requests_ckpt.json")
    _write_requests(reqs, (24, 64), 8)
    out = serve.main(["--arch", ARCH, "--sparse", "--ckpt-dir", ckpt,
                      "--engine", "--requests", reqs])
    _check_completions(out, (24, 64), 8)
    return {"loss_first": losses[0], "loss_last": losses[-1],
            "spc_steps": len(hist_spc), "debias_steps": len(hist_db),
            "bcsr_layers_with_blocks": sum(1 for v in blocks.values() if v),
            "bcsr_bytes": report["bcsr_bytes"],
            "dense_bytes": report["dense_bytes"]}


def phase_four_chips(tmp):
    """launch/serve's smollm-360m ``--sparse`` prefill logits under
    ``--mesh 2,2`` against the same run on one device, both through
    ``serve.main --logits-out`` in this process (the CI parity check), and
    how the entry point's compiled sharded prefill partitions the kernels."""
    import numpy as np

    from repro.launch import serve

    hlo = os.path.join(ROOT, "chiprun_out", "four_chip_prefill.hlo.txt")
    os.makedirs(os.path.dirname(hlo), exist_ok=True)
    logits = {}
    for name, flags in (("sharded", ["--mesh", "2,2", "--hlo-out", hlo]),
                        ("one_device", [])):
        path = os.path.join(tmp, f"{name}.npy")
        serve.main(["--arch", ARCH, "--sparse", "--batch", "4",
                    "--prompt-len", "128", "--gen", "1", *flags,
                    "--logits-out", path])
        logits[name] = np.load(path)
    with open(hlo) as f:
        lines = f.read().split("\n")
    n_kernel = sum('custom_call_target="tpu_custom_call"' in l for l in lines)
    gathers = [l.split(" = ", 1)[1].split(" ", 1)[0] for l in lines
               if " all-gather(" in l or " all-gather-start(" in l]
    err = float(np.max(np.abs(logits["sharded"].astype(np.float64)
                              - logits["one_device"])))
    check(n_kernel > 0, "the sharded prefill holds no tpu_custom_call")
    check(err < 1e-2, f"sharded vs one-device prefill logits max|diff| "
                      f"{err:.4g}")
    return {"max_abs_diff": err, "tpu_custom_calls": n_kernel,
            "all_gathers": len(gathers),
            "all_gather_shapes": sorted(set(gathers)),
            "hlo": "chiprun_out/four_chip_prefill.hlo.txt"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --mesh 2,2 sharded prefill parity")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: FAIL: no src/repro next to {__file__}; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    stats = CompileStats()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        chips = 4 if args.four_chips else 1
        dev = jax.devices()[0]
        run_phase("device", lambda: phase_device(chips), stats, dev)
        if args.four_chips:
            run_phase("four_chips", lambda: phase_four_chips(tmp), stats,
                      dev)
        else:
            run_phase("kernels", phase_kernels, stats, dev)
            run_phase("serve", lambda: phase_serve(tmp), stats, dev)
            run_phase("train", lambda: phase_train(tmp), stats, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    secs, hits, misses = stats.snapshot()
    print(f"[chip_smoke] compile cache {cache_dir}: {secs:.1f}s backend "
          f"compile, {hits} hits, {misses} misses", flush=True)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
