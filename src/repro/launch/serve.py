"""Serving launcher: batched generation, optionally end-to-end from
compressed (BCSR) weights — the paper's inference path.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --batch 4 --prompt-len 16 --gen 32 --sparse

``--sparse`` (without a checkpoint) block-magnitude-prunes the random-init
model on the serving BCSR grid, builds ``CompressedParams`` (attention
QKV/O, MLP, and untied head as BlockCSR; dense fallback for matrices that
don't compress) and serves from it: every compressed projection dispatches
``sparse_matmul`` on the prefill and decode paths. Add ``--quantize-bits
8|4`` for Deep Compression stage 2: block data is palette-quantized and
served from ``PaletteBCSR`` (uint8 / nibble-packed codes, palette lookup
fused into the kernel).

``--ckpt-dir <dir>`` instead serves the full trained pipeline's artifact —
a compressed checkpoint written by ``launch/train --sparse`` (prox-SpC
trained into BlockCSR, mask-frozen debias retraining on the compressed
params, optionally palette-quantized) — template-free and without
densifying; no pruning happens here because the sparsity came from
training. The manifest's arch/reduced tags are validated against the serve
flags.

Either way the per-layer size breakdown (``compression_summary``) and the
one-line dense/bcsr/palette byte report are printed; every number follows
docs/size_accounting.md.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config
from repro.core.metrics import model_size_bytes
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_flag
from repro.models.model_zoo import build
from repro.serve.step import generate
from repro.sparse.compress import (CompressionPlan, compress_params,
                                   compressed_size_bytes, compression_summary,
                                   format_size_report, iter_bcsr,
                                   prune_blocks_for_plan)
from repro.sparse.formats import PaletteBCSR


def _report_sizes(cp, dense_b: int):
    """Per-layer breakdown + one-line byte report (docs/size_accounting.md):
    ``bcsr`` is always the fp32 BlockCSR total; when any layer is
    palette-quantized the actual (smaller) serving total is reported as
    ``palette``."""
    from repro.sparse.compress import bcsr_equiv_size_bytes

    actual_b = compressed_size_bytes(cp)
    bcsr_b = bcsr_equiv_size_bytes(cp)
    quantized = any(isinstance(m, PaletteBCSR) for _, m in iter_bcsr(cp))
    print(compression_summary(cp))
    print(format_size_report(dense_b, bcsr_b,
                             actual_b if quantized else None))


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--sparse", action="store_true",
                    help="serve from the compressed form: with --ckpt-dir, "
                         "load a launch/train --sparse artifact (prox-SpC "
                         "trained into BlockCSR, mask-frozen debias, "
                         "optionally palette-quantized) template-free; "
                         "without one, block-prune the random init on the "
                         "serving BCSR grid and compress it")
    ap.add_argument("--quantize-bits", type=int, default=0, choices=[0, 4, 8],
                    help="palette-quantize the compressed block data "
                         "(PaletteBCSR, Deep Compression stage 2) before "
                         "serving — prune path only; checkpoints carry "
                         "their own quantization")
    ap.add_argument("--sparsity", type=float, default=0.9,
                    help="fraction of weight blocks pruned before compression")
    ap.add_argument("--block", type=int, nargs=2,
                    default=CompressionPlan.block, metavar=("BR", "BC"),
                    help="BCSR block (out, in) view; the compiled TPU "
                         "kernels need multiples of 128")
    ap.add_argument("--min-block-sparsity", type=float, default=0.5,
                    help="dense fallback below this zero-block fraction")
    ap.add_argument("--ckpt-dir", default="",
                    help="serve a compressed checkpoint from launch/train "
                         "--sparse (looks in <dir>/compressed, then <dir>)")
    ap.add_argument("--mesh", default="none",
                    help="none | single | multi | DATA,MODEL. Serve under "
                         "an SPMD mesh: 'single'/'multi' are the production "
                         "pod meshes, 'D,M' a host mesh (the multi-device "
                         "CI runs --mesh 2,2 on 4 forced host devices). "
                         "Compressed (--sparse / --ckpt-dir) serving shards "
                         "the BCSR/PaletteBCSR pytree: block stores split "
                         "along the block-row slot axis per the dense "
                         "out-dim rule, index/gather tables and palettes "
                         "replicate, and prefill/decode run the same "
                         "sparse_matmul kernels under GSPMD — logits match "
                         "the unsharded run")
    ap.add_argument("--logits-out", default="",
                    help="save the prefill logits (B, vocab) to this .npy "
                         "path — the CI sharded-vs-single-host parity gate "
                         "compares these to 1e-4")
    ap.add_argument("--hlo-out", default="",
                    help="with --logits-out: also save that prefill's "
                         "compiled HLO text (how a --mesh run partitions "
                         "the kernels and what it gathers)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation when sampling (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus (top-p) filtering when sampling (1 = off)")
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(serve/engine.py): slot resource pools (paged KV "
                         "for attention incl. int8, slot-indexed state for "
                         "RWKV/RG-LRU), chunked prefill, priority classes "
                         "with preempt-and-requeue over a fixed-capacity "
                         "slot batch — many concurrent mixed-length "
                         "requests instead of one fixed batch")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="engine slot capacity (concurrent requests)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens consumed per slot per engine tick; "
                         "prompts longer than this prefill across ticks, "
                         "interleaved with running decodes")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page length (tokens) of the paged cache")
    ap.add_argument("--first-chunk", type=int, default=0,
                    help="jumbo width for the FIRST prefill chunk of a "
                         "long prompt (> --prefill-chunk; 0 = off) — a "
                         "third compiled tick width that cuts TTFT")
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "pallas", "ref"],
                    help="paged-attention kernel for the engine step: "
                         "'pallas' = fused page-gather flash-decode kernel "
                         "(interpret mode off-TPU), 'ref' = jnp gather "
                         "oracle, 'auto' = pallas on TPU, ref elsewhere")
    ap.add_argument("--kv-splits", type=int, default=1,
                    help="flash-decode KV-split lanes per slot on the "
                         "pallas backend")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="with --engine: radix-tree prefix caching — "
                         "requests sharing a prompt prefix share physical "
                         "KV pages (refcounted, copy-on-write on the first "
                         "diverging page), so a shared system prompt is "
                         "prefilled once (attention-layer models only)")
    ap.add_argument("--priority", default="standard",
                    help="default scheduling class for --engine requests: "
                         "interactive | standard | batch, or an int >= 0 "
                         "(0 = most important; lower classes can be "
                         "preempted). Per-request override via the "
                         '--requests file\'s "priority" field')
    ap.add_argument("--requests", default="",
                    help="JSON request mix for --engine: a list of "
                         '{"prompt_len": N, "gen": M} (random prompt) or '
                         '{"prompt": [ids], "gen": M} entries, each with an '
                         'optional "priority" (class name or int >= 0, '
                         "default --priority); default mix is --batch "
                         "copies of --prompt-len/--gen")
    ap.add_argument("--parity-check", action="store_true",
                    help="with --engine (greedy): also run every request "
                         "through the sequential generate() path and fail "
                         "on any per-token mismatch")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --engine: serve through N data-parallel "
                         "engine replicas behind the asyncio router "
                         "(serve/router.py). Replicas share the same "
                         "immutable (compressed) params — the smaller the "
                         "model, the more replicas fit per host")
    ap.add_argument("--metrics-out", default="",
                    help="with --engine: write the live metrics registry "
                         "after the run — Prometheus text exposition, or a "
                         "JSON snapshot when the path ends in .json "
                         "(scheduler admissions/preemptions, page occupancy, "
                         "prefix-cache hits, per-tick widths, router "
                         "dispatch/failover)")
    ap.add_argument("--trace-out", default="",
                    help="with --engine: write a Chrome trace-event / "
                         "Perfetto JSON of the run (per-request lifecycle "
                         "spans + the engine's tick-phase spans) — load it "
                         "at https://ui.perfetto.dev")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="with --engine: record a jax.profiler trace of the "
                         "run into DIR: device ops named by the model's "
                         "scopes, and the engine's tick-phase spans on the "
                         "same clock (open with TensorBoard/XProf or "
                         "jax.profiler.ProfileData)")
    ap.add_argument("--route", default="prefix",
                    choices=["prefix", "least-loaded", "round-robin"],
                    help="router dispatch policy: 'prefix' = "
                         "rendezvous-hash the leading page-aligned prompt "
                         "tokens so shared system prompts stay on the "
                         "replica whose radix prefix cache holds them "
                         "(falls back to least-loaded for short prompts "
                         "and failed replicas), 'least-loaded' = queue "
                         "depth + reserved pages, 'round-robin' = modulo "
                         "counter")
    args = ap.parse_args(argv)
    if args.quantize_bits and (not args.sparse or args.ckpt_dir):
        raise SystemExit(
            "--quantize-bits applies to the --sparse prune path only "
            "(checkpoints carry their own quantization; without --sparse "
            "nothing is compressed to quantize)")
    if args.hlo_out and not args.logits_out:
        raise SystemExit("--hlo-out saves the --logits-out prefill's HLO; "
                         "pass --logits-out too")

    model = build(args.arch, reduced=args.reduced)
    cfg = model.cfg
    key = jax.random.PRNGKey(0)
    mesh = mesh_from_flag(args.mesh)
    mesh_ctx = shd.use_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()

    if args.ckpt_dir:
        # --ckpt-dir always means "serve this compressed checkpoint" (with
        # or without --sparse): silently serving random init instead of the
        # artifact the user pointed at would be a footgun
        cdir = os.path.join(args.ckpt_dir, "compressed")
        if not os.path.isdir(cdir):
            cdir = args.ckpt_dir
        ckpt = Checkpointer(cdir)
        latest = ckpt.latest_step()
        if latest is None:
            raise SystemExit(f"no checkpoints found in {cdir}")
        extra = ckpt.manifest(latest).get("extra") or {}
        if extra.get("arch") not in (None, args.arch) or \
                extra.get("reduced") not in (None, args.reduced):
            raise SystemExit(
                f"checkpoint was trained with arch={extra.get('arch')!r} "
                f"reduced={extra.get('reduced')} but serve got "
                f"arch={args.arch!r} reduced={args.reduced}")
        params = ckpt.restore_compressed(mesh=mesh)
        # dense byte count from shapes only — don't allocate a dense model
        # just to print the ratio
        shapes = jax.eval_shape(model.init, key)
        dense_b = sum(int(l.size) * l.dtype.itemsize
                      for l in jax.tree.leaves(shapes))
        _report_sizes(params, dense_b)
    elif args.sparse:
        params = model.init(key)
        plan = CompressionPlan(
            block=tuple(args.block), min_sparsity=args.min_block_sparsity,
            quantize_bits=args.quantize_bits or None,
            # pack slot counts so the block store divides the mesh axes and
            # shards (instead of silently replicating on odd slot counts)
            slot_multiple=(int(np.lcm.reduce(
                [int(s) for s in mesh.shape.values()]))
                if mesh is not None else None))
        params = prune_blocks_for_plan(params, plan, args.sparsity)
        dense_b = model_size_bytes(params, sparse=False)
        params = compress_params(params, plan)   # PaletteBCSR when quantizing
        _report_sizes(params, dense_b)
    else:
        params = model.init(key)

    if mesh is not None and not args.ckpt_dir:
        # checkpoint restore placed sharded already (restore_compressed);
        # the prune/dense paths place here — dense rules for raw leaves,
        # block-row slot sharding for BCSR/PaletteBCSR stores
        params = jax.device_put(params, shd.param_shardings(params, mesh))

    prompt = jax.random.randint(key, (args.batch, args.prompt_len),
                                0, cfg.vocab)
    with mesh_ctx:
        if args.logits_out:
            cache = model.init_cache(args.batch, args.prompt_len + args.gen)
            prefill = jax.jit(model.prefill).lower(params, prompt,
                                                   cache).compile()
            logits, _ = prefill(params, prompt, cache)
            np.save(args.logits_out,
                    np.asarray(jax.device_get(logits)).astype(np.float32))
            print(f"prefill logits -> {args.logits_out}")
            if args.hlo_out:
                with open(args.hlo_out, "w") as f:
                    f.write(prefill.as_text())
                print(f"prefill HLO -> {args.hlo_out}")
        if args.engine:
            return _run_engine(model, params, args)
        t0 = time.perf_counter()
        out = generate(model, params, prompt, args.gen,
                       sampling=_sampling(args), rng=jax.random.PRNGKey(1))
        dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", out[0, :16].tolist())
    return out


def _sampling(args):
    """The one place serve flags become a typed SamplingParams."""
    from repro.serve.api import SamplingParams
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p)


def _load_requests(args, vocab: int) -> list:
    """``api.Request`` list from --requests JSON (or the --batch/
    --prompt-len/--gen defaults), validated against the typed schema
    (``api.parse_request_file``) with actionable errors. Random prompts are
    seeded per request index so the mix is reproducible."""
    import json

    from repro.serve import api

    if args.requests:
        with open(args.requests) as f:
            try:
                spec = json.load(f)
            except json.JSONDecodeError as e:
                raise SystemExit(f"--requests {args.requests}: not valid "
                                 f"JSON ({e})")
    else:
        spec = [{"prompt_len": args.prompt_len, "gen": args.gen}
                for _ in range(args.batch)]
    try:
        entries = api.parse_request_file(spec, default_gen=args.gen,
                                         default_priority=args.priority)
    except api.ApiValidationError as e:
        raise SystemExit(f"--requests {args.requests or '(defaults)'}: {e}")
    out = []
    for i, e in enumerate(entries):
        ids = e["prompt"]
        if ids is None:
            ids = np.asarray(jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(1234), i),
                (e["prompt_len"],), 0, vocab), np.int32)
        out.append(api.Request(prompt=ids,
                               max_new_tokens=e["max_new_tokens"],
                               eos_id=e["eos_id"], priority=e["priority"],
                               sampling=e["sampling"]))
    return out


def _engine_config(args, max_seq: int):
    """The one place serve flags become an ``EngineConfig`` — also what
    the router replicates (all replicas share this single value)."""
    from repro.serve.engine import EngineConfig
    return EngineConfig(max_batch=args.max_batch,
                        prefill_chunk=args.prefill_chunk,
                        page_size=args.page_size, max_seq_len=max_seq,
                        first_chunk=args.first_chunk or None,
                        attn_backend=args.attn_backend,
                        kv_splits=args.kv_splits,
                        prefix_cache=args.prefix_cache,
                        sampling=_sampling(args))


def _ms(x) -> str:
    """Milliseconds for printing — percentiles over an empty record set
    are None (obs.metrics.pct), shown as '-'."""
    return "-" if x is None else f"{x * 1e3:.0f}"


def _print_slo_classes(s):
    if len(s["by_class"]) > 1 or s.get("n_preemptions"):
        for c, cs in s["by_class"].items():
            print(f"  class {c}: {cs['n_requests']} requests "
                  f"({cs['n_preempted']} preempted) | ttft p50/p95 "
                  f"{_ms(cs['ttft_p50_s'])}/{_ms(cs['ttft_p95_s'])}ms"
                  f" | latency p50/p95 {_ms(cs['latency_p50_s'])}/"
                  f"{_ms(cs['latency_p95_s'])}ms")


def _telemetry(args):
    """--trace-out / --profile flags to (tracer, profiling context): the
    tracer is None when --trace-out is off (the engine's spans are then
    profiler annotations only); the context brackets the run with
    ``jax.profiler.trace(DIR)`` under --profile DIR."""
    from repro.obs import Tracer
    tracer = Tracer() if args.trace_out else None
    profiling = (jax.profiler.trace(args.profile) if args.profile
                 else contextlib.nullcontext())
    return tracer, profiling


def _save_telemetry(args, save_prom, save_json, tracer):
    """Write --metrics-out / --trace-out artifacts. ``save_prom(path)`` /
    ``save_json(path)`` are the caller's exporters (engine registry, or the
    router's merged fleet exposition)."""
    if args.metrics_out:
        if args.metrics_out.endswith(".json"):
            save_json(args.metrics_out)
        else:
            save_prom(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out and tracer is not None:
        tracer.save(args.trace_out)
        print(f"trace -> {args.trace_out} ({len(tracer.events)} events)")
    if args.profile:
        print(f"profile -> {args.profile}")


def _check_parity(model, params, args, requests, results):
    if args.temperature > 0:
        raise SystemExit("--parity-check needs greedy decoding "
                         "(--temperature 0): generate() and the engine "
                         "draw from different rng streams")
    for rid, r in enumerate(requests):
        ids, gen = r.prompt_ids, r.max_new_tokens
        ref = np.asarray(generate(model, params, ids[None, :], gen))[0]
        got = np.asarray(results[rid])
        if r.eos_id is not None and r.eos_id in ref.tolist():
            ref = ref[:ref.tolist().index(r.eos_id) + 1]
        if not np.array_equal(ref, got):
            raise SystemExit(
                f"engine-vs-generate token mismatch for request {rid} "
                f"(prompt_len={len(ids)}): {got.tolist()} != "
                f"{ref.tolist()}")
    print(f"engine-vs-generate parity OK ({len(requests)} requests)")


def _run_engine(model, params, args):
    """The --engine path: continuous batching over the slot resource pools
    (paged KV for attention layers, slot-indexed state for recurrent);
    with --replicas N > 1, N such engines behind the asyncio router."""
    from repro.serve.engine import ServeEngine

    requests = _load_requests(args, model.cfg.vocab)
    max_seq = max(len(r.prompt) + r.max_new_tokens for r in requests)
    config = _engine_config(args, max_seq)
    try:
        if args.replicas > 1:
            return _run_router(model, params, args, config, requests)
        tracer, profiling = _telemetry(args)
        engine = ServeEngine(model, params, config,
                             rng=jax.random.PRNGKey(1), tracer=tracer)
    except NotImplementedError as e:
        raise SystemExit(f"--engine: {e}")
    pb = engine.pool_bytes
    print(f"engine pools: kv_pages={pb['kv_page_bytes'] / 2**20:.2f} MiB "
          f"recurrent_state={pb['state_slot_bytes'] / 2**20:.2f} MiB "
          f"({engine.config.max_batch} slots)")
    from repro.serve.api import ApiValidationError
    try:
        with profiling:
            out = engine.run(requests)
    except ApiValidationError as e:
        raise SystemExit(f"--engine: {e}")
    s = out["stats"]
    print(f"engine: {s['n_requests']} requests "
          f"({s['n_prompt']} prompt + {s['n_generated']} new tokens) in "
          f"{s['wall_s']:.2f}s = {s['tok_s']:.1f} tok/s | "
          f"ttft p50/p95 {_ms(s['ttft_p50_s'])}/{_ms(s['ttft_p95_s'])}ms"
          f" | latency p50/p95 {_ms(s['latency_p50_s'])}/"
          f"{_ms(s['latency_p95_s'])}ms | {s['n_ticks']} ticks, "
          f"{s['n_prefill_chunks']} prefill chunks | pools "
          f"kv={s['kv_page_bytes']} state={s['state_slot_bytes']} bytes")
    if len(s["by_class"]) > 1 or s["n_preemptions"]:
        _print_slo_classes(s)
        print(f"  {s['n_preemptions']} preemptions")
    if args.prefix_cache:
        print(f"  prefix cache: hit rate {s['prefix_hit_rate']:.1%} "
              f"({s['n_cached_tokens']} prompt tokens served from cache)")
    _save_telemetry(args, engine.metrics.save_prometheus,
                    engine.metrics.save_json, tracer)
    print("sample:", [int(t) for t in out["results"][0][:16]])
    if args.parity_check:
        _check_parity(model, params, args, requests, out["results"])
    out["engine"] = engine
    return out


def _run_router(model, params, args, config, requests):
    """--replicas N: N identical engines (one EngineConfig, shared params)
    behind the prefix-affinity/least-loaded/round-robin router."""
    from repro.serve.engine import ServeEngine
    from repro.serve.router import Router

    tracer, profiling = _telemetry(args)
    # one tracer across the fleet: router rids are globally unique, so
    # every request still gets exactly one track
    engines = [ServeEngine(model, params, config, tracer=tracer)
               for _ in range(args.replicas)]
    router = Router(engines, policy=args.route)
    with profiling:
        out = router.serve(requests)
    s = out["stats"]
    print(f"router[{args.replicas}x {args.route}]: {s['n_requests']} "
          f"requests ({s['n_prompt']} prompt + {s['n_generated']} new "
          f"tokens) in {s['wall_s']:.2f}s = {s['tok_s']:.1f} tok/s | "
          f"ttft p50/p95 {_ms(s['ttft_p50_s'])}/{_ms(s['ttft_p95_s'])}ms"
          f" | latency p50/p95 {_ms(s['latency_p50_s'])}/"
          f"{_ms(s['latency_p95_s'])}ms | "
          f"{s['n_redispatched']} re-dispatched, "
          f"{s['n_failed_replicas']} failed replicas")
    _print_slo_classes(s)
    for r in s["per_replica"]:
        line = (f"  replica {r['replica']}: {r['n_requests']} requests, "
                f"{r['n_generated']} tokens, {r['n_ticks']} ticks")
        if args.prefix_cache:
            line += f", prefix hit rate {r['prefix_hit_rate']:.1%}"
        if r["failed"]:
            line += " [FAILED]"
        print(line)

    def save_json(path):
        import json
        with open(path, "w") as f:
            json.dump({"router": router.metrics.snapshot(),
                       "replicas": [r.engine.metrics.snapshot()
                                    for r in router.replicas]}, f, indent=1)
            f.write("\n")

    def save_prom(path):
        with open(path, "w") as f:
            f.write(router.to_prometheus())

    _save_telemetry(args, save_prom, save_json, tracer)
    print("sample:", [int(t) for t in out["results"][0][:16]])
    if args.parity_check:
        _check_parity(model, params, args, requests, out["results"])
    return out


if __name__ == "__main__":
    main()
