"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --steps 200 --compress l1:2.0 --debias-steps 50 --ckpt-dir /tmp/ckpt

Runs compressed training (the paper's SpC pipeline) on any zoo architecture.
On this CPU container use --reduced; on a pod, point --mesh at the production
mesh and the same script drives all hosts (SPMD).

``--sparse`` switches to SpC-Retrain (train *into* BlockCSR): the prox is the
plan-aligned block group-l1 (exact zero blocks on the serving (out, in) BCSR
grid), compression happens WITHOUT a prune step, the debias phase retrains
the compressed model itself (masks frozen, only BlockCSR.data updates, dw via
SDDMM at resident slots), and the final artifact is a compressed checkpoint
under ``<ckpt-dir>/compressed`` that ``launch/serve --sparse --ckpt-dir``
loads and serves from directly:

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --sparse --steps 60 --debias-steps 20 --compress group_l1:0.05 \
        --ckpt-dir /tmp/spc

``--quantize-bits 8|4`` adds Deep Compression stage 2 on top: after debias,
the BlockCSR block data is k-means palette-quantized (``PaletteBCSR``,
uint8 / nibble-packed codes + per-layer palette) and the compressed
checkpoint stores — and serving loads — the quantized form directly.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config
from repro.core import metrics as metrics_lib
from repro.core.optimizers import prox_adam, prox_rmsprop, prox_sgd
from repro.data.synthetic import TokenStreamConfig, token_batch
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_flag
from repro.models import frontends
from repro.models.model_zoo import build
from repro.sparse.compress import (CompressionPlan, compressed_size_bytes,
                                   compression_summary, format_size_report,
                                   make_plan_prox, quantize_compressed)
from repro.train.loop import (LoopConfig, run_spc_pipeline,
                              run_spc_retrain_pipeline, train_loop)
from repro.train.state import TrainState
from repro.train.step import make_train_step


def parse_compress(spec: str):
    """'l1:2.0' | 'group_l1:0.5' | 'none'."""
    if spec == "none":
        return "none", 0.0
    kind, lam = spec.split(":")
    return kind, float(lam)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--debias-steps", type=int, default=0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress", default="l1:1.0")
    ap.add_argument("--optimizer", default="prox_adam",
                    choices=["prox_adam", "prox_rmsprop", "prox_sgd"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--mesh", default="none",
                    help="none | single | multi | DATA,MODEL. SPMD training "
                         "mesh: 'single'/'multi' are the production pod "
                         "meshes, 'D,M' a host mesh over existing devices "
                         "(CI forces 4 with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=4 and runs --mesh 2,2). "
                         "With --sparse the COMPRESSED pytree is sharded "
                         "too: BlockCSR/PaletteBCSR block stores split "
                         "along the block-row slot axis (the dense out-dim "
                         "rule), index/gather tables and palettes "
                         "replicate, and the sharded CompressedParams "
                         "flows through debias retraining and the "
                         "compressed checkpoint unchanged")
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--telemetry-out", default="",
                    help="stream per-log-step training telemetry to this "
                         "JSONL file: loss/grad-norm metrics, the group-l1 "
                         "penalty, live per-layer block sparsity on the "
                         "serving BCSR grid, and debias progress — one "
                         "phase-tagged record per line, flushed as written "
                         "(tail-able while training runs)")
    ap.add_argument("--sparse", action="store_true",
                    help="SpC-Retrain into BlockCSR: prox-SpC training with "
                         "plan-aligned block group-l1 (exact zero blocks on "
                         "the serving (out, in) BCSR grid, no prune step), "
                         "then mask-frozen debias retraining ON the "
                         "compressed params (only BlockCSR.data updates, dw "
                         "via SDDMM at resident slots), then a compressed "
                         "checkpoint under <ckpt-dir>/compressed that "
                         "launch/serve --sparse --ckpt-dir loads "
                         "template-free")
    ap.add_argument("--quantize-bits", type=int, default=0, choices=[0, 4, 8],
                    help="Deep Compression stage 2 (with --sparse): after "
                         "debias, k-means palette-quantize BlockCSR block "
                         "data to PaletteBCSR at this bit width (0 = off); "
                         "the checkpoint then serves from the quantized "
                         "form directly")
    ap.add_argument("--block", type=int, nargs=2,
                    default=CompressionPlan.block, metavar=("BR", "BC"),
                    help="BCSR block on the (out, in) view (--sparse); the "
                         "compiled TPU kernels need multiples of 128")
    ap.add_argument("--min-block-sparsity", type=float, default=0.3,
                    help="dense fallback below this zero-block fraction")
    args = ap.parse_args(argv)
    if args.quantize_bits and not args.sparse:
        raise SystemExit("--quantize-bits requires --sparse (the palette "
                         "quantizes the compressed BlockCSR block store)")

    logging.basicConfig(level=logging.INFO)
    cfg = get_config(args.arch)
    model = build(cfg, reduced=args.reduced)
    cfg = model.cfg
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,}")

    kind, lam = parse_compress(args.compress)
    opt_cls = {"prox_adam": prox_adam, "prox_rmsprop": prox_rmsprop,
               "prox_sgd": prox_sgd}[args.optimizer]

    plan = CompressionPlan(block=tuple(args.block),
                           min_sparsity=args.min_block_sparsity)
    if args.sparse:
        # SpC-Retrain: block group-l1 on the exact compression grid — the
        # regularizer, not a prune pass, creates the BCSR zero blocks
        if kind != "group_l1" or lam <= 0:
            raise SystemExit(
                f"--sparse trains into BlockCSR via block group-l1; pass "
                f"--compress group_l1:<lam> with lam > 0 (got {args.compress!r})")
        opt = opt_cls(args.lr, lam=lam, prox_fn=make_plan_prox(plan))
    else:
        opt = opt_cls(args.lr, lam=lam,
                      prox_name=kind if kind != "none" else "none")
    opt_debias = opt_cls(args.lr, lam=0.0)

    data_cfg = TokenStreamConfig(vocab=cfg.vocab, seq_len=args.seq,
                                 global_batch=args.batch)

    def batch_fn(step):
        b = token_batch(data_cfg, step)
        if cfg.frontend != "none":
            emb = frontends.synthetic_embeddings(
                jax.random.PRNGKey(step), cfg, args.batch, args.seq)
            b = {"inputs": emb, "labels": b["labels"]}
        return b

    mesh = mesh_from_flag(args.mesh)
    if mesh is not None:
        # place master params once; train steps then carry the shardings
        # (the compressed pipeline re-places after compress_params — see
        # run_spc_retrain_pipeline)
        params = jax.device_put(params, shd.param_shardings(params, mesh))

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None:
        # train_loop resumes from the newest checkpoint: compression/prox
        # flags are NOT re-applied to already-trained steps, so a rerun
        # with different hyperparameters into the same dir silently keeps
        # the old trajectory (at latest_step >= --steps the SpC phase is
        # skipped entirely). Make that visible.
        logging.getLogger("repro.launch.train").warning(
            "resuming from existing checkpoint (step %d) in %s — "
            "hyperparameter flags must match the original run; use a fresh "
            "--ckpt-dir to restart training", ckpt.latest_step(),
            args.ckpt_dir)

    def make_step(o, param_transform=None):
        step = make_train_step(model, o, param_transform=param_transform)
        return jax.jit(step, donate_argnums=(0,))

    telemetry = None
    if args.telemetry_out:
        from repro.obs import TrainTelemetry
        telemetry = TrainTelemetry(args.telemetry_out)

    ctx = shd.use_mesh(mesh) if mesh is not None else _null_ctx()
    with ctx:
        if args.sparse:
            cp, hist_spc, hist_db, report = run_spc_retrain_pipeline(
                params, make_step, opt, opt_debias, batch_fn,
                spc_steps=args.steps, debias_steps=args.debias_steps,
                plan=plan, checkpointer=ckpt, log_every=args.log_every,
                telemetry=telemetry)
            if args.quantize_bits:
                # Deep Compression stage 2, the LAST stage: quantize after
                # debias so retraining saw fp block data; the checkpoint
                # below stores (and serve loads) the palette form directly
                cp = quantize_compressed(cp, bits=args.quantize_bits)
                report["palette_bytes"] = compressed_size_bytes(cp)
            print("compression:", json.dumps(report, indent=1))
            if hist_spc:
                print(f"loss: {hist_spc[0]['loss']:.4f} -> "
                      f"{hist_spc[-1]['loss']:.4f}")
            print(compression_summary(cp))
            print(format_size_report(report["dense_bytes"],
                                     report["bcsr_bytes"],
                                     report.get("palette_bytes")))
            if args.ckpt_dir:
                cdir = os.path.join(args.ckpt_dir, "compressed")
                final_step = args.steps + args.debias_steps
                path = Checkpointer(cdir, keep_n=2).save(
                    final_step, cp,
                    extra={"plan": dataclasses.asdict(cp.plan),
                           "arch": args.arch, "reduced": args.reduced})
                print(f"compressed checkpoint: {path}")
            if telemetry is not None:
                telemetry.close()
                print(f"telemetry: {telemetry.n_records} records -> "
                      f"{args.telemetry_out}")
            return cp, hist_spc, hist_db, report

        state, hist_spc, hist_db, report = run_spc_pipeline(
            params, make_step, opt, opt_debias, batch_fn,
            spc_steps=args.steps, debias_steps=args.debias_steps,
            checkpointer=ckpt, log_every=args.log_every,
            telemetry=telemetry, sparsity_block=tuple(args.block))

    print("compression:", json.dumps(report, indent=1))
    if hist_spc:
        print(f"loss: {hist_spc[0]['loss']:.4f} -> {hist_spc[-1]['loss']:.4f}")
    table = metrics_lib.layer_compression(state.params)
    print(metrics_lib.format_table(table, "layer-wise compression:"))
    if telemetry is not None:
        telemetry.close()
        print(f"telemetry: {telemetry.n_records} records -> "
              f"{args.telemetry_out}")
    return state, hist_spc, hist_db, report


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
