"""Continuous-batching engine benchmark: aggregate tokens/s and p50/p95
latency at several request mixes, engine vs the sequential single-request
``generate`` path, on dense / BlockCSR / PaletteBCSR weights — for the
attention reference arch (smollm) and the recurrent archs the slot-state
pools brought under the engine (rwkv6-3b, recurrentgemma-9b). Each row
also records the pool byte split (KV pages vs recurrent state slots).

The headline number is the batching win on the compressed serving path:
one engine tick decodes every active slot in a single jitted dispatch,
so aggregate compressed-decode tokens/s should beat running the same
requests one-by-one through ``generate`` (whose per-token dispatch cost is
the same but amortized over batch=1).

    PYTHONPATH=src python -m benchmarks.serve_engine --json BENCH_engine.json

Rows follow the BENCH json schema (``name`` / ``us_per_call`` /
``derived``), same as ``benchmarks.inference_speedup`` — CI uploads the
JSON alongside ``BENCH_pr.json``. ``--assert-speedup`` exits nonzero if
the batched compressed engine fails to beat sequential compressed serving
(the acceptance gate for the engine's reason to exist), and also gates the
router lanes (serve/router.py): 2-replica aggregate tokens/s scaling,
prefix-affinity retention of the warm-TTFT win vs the round-robin control,
and per-token parity across a forced replica failure + re-dispatch.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# request mixes: (name, [(prompt_len, gen), ...])
MIXES = {
    "decode_heavy": [(8, 24)] * 8,
    "mixed_len": [(8, 16)] * 4 + [(48, 16)] * 4,
    "prefill_heavy": [(64, 8)] * 8,
}
# shared-prefix mix: a 96-token shared system prompt + 8-token distinct
# tails (page_size 16 -> the shared prefix is exactly 6 immutable pages)
SHARED_PREFIX = dict(n=8, shared_len=96, tail_len=8, gen=8, page_size=16)
# router lanes: replica scaling on a decode-heavy mix, prefix-affinity
# retention on the shared-prefix mix, forced-failure re-dispatch parity
ROUTER = dict(n=16, prompt_len=8, gen=24, max_batch=8, page_size=16)
# recurrent archs ride the decode-heavy mix (state pools are O(1) per
# slot, so decode is where the slot-batching win lives)
RECURRENT_ARCHS = ("rwkv6-3b", "recurrentgemma-9b")


def _requests(mix, vocab: int):
    import jax

    out = []
    for i, (plen, gen) in enumerate(mix):
        ids = np.asarray(jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(1234), i), (plen,),
            0, vocab), np.int32)
        out.append((ids, gen))
    return out


def _engine_stats(model, params, requests, *, max_batch=8, prefill_chunk=16,
                  page_size=16):
    """Warm run (compile both tick widths) then a timed run on the same
    engine instance — the jitted mixed step is per-engine, so reuse keeps
    compile time out of the measurement."""
    from repro.serve.engine import EngineConfig, ServeEngine

    max_seq = max(len(p) + g for p, g in requests)
    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=max_batch,
                                   prefill_chunk=prefill_chunk,
                                   page_size=page_size, max_seq_len=max_seq))
    eng.run(requests)                       # warm-up: compiles + first pass
    runs = [eng.run(requests)["stats"] for _ in range(2)]
    best = max(runs, key=lambda s: s["tok_s"])   # best-of-2: shave OS noise
    return best, eng


def _sequential_tok_s(model, params, requests):
    """Single-request baseline: the same requests served one at a time
    through persistent jitted prefill/decode (compile excluded — shapes are
    warmed before timing)."""
    import jax
    import jax.numpy as jnp

    from repro.serve.step import make_decode_step

    prefill = jax.jit(model.prefill)
    decode = jax.jit(make_decode_step(model))

    def one(ids, gen):
        cache = model.init_cache(1, len(ids) + gen)
        logits, cache = prefill(params, jnp.asarray(ids)[None, :], cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for t in range(len(ids), len(ids) + gen - 1):
            tok, _, cache = decode(params, tok[:, None], cache, jnp.int32(t))
        return tok

    for ids, gen in requests:               # warm every (shape) variant
        jax.block_until_ready(one(ids, gen))
    best = float("inf")
    for _ in range(2):                      # best-of-2: shave OS noise
        t0 = time.perf_counter()
        for ids, gen in requests:
            jax.block_until_ready(one(ids, gen))
        best = min(best, time.perf_counter() - t0)
    return sum(g for _, g in requests) / best


def _shared_prefix_row(model, params, fmt: str):
    """Prefix-cache lane: three waves through ONE engine. Wave A warms the
    compile caches (and populates the radix tree with its own prefix);
    wave B runs a fresh shared prefix cold (no hits); wave C reuses wave
    B's prefix with new tails (hits). ``prefix_ttft_speedup`` = cold p50
    TTFT / warm p50 TTFT — a same-run ratio, so machine speed cancels."""
    import jax

    from repro.serve.engine import EngineConfig, ServeEngine

    sp = SHARED_PREFIX
    vocab = model.cfg.vocab

    def rand(tag: str, n: int):
        key = jax.random.PRNGKey(abs(hash(tag)) % 2**31)
        return np.asarray(jax.random.randint(key, (n,), 0, vocab), np.int32)

    def wave(prefix_tag: str, tail_tag: str):
        shared = rand(prefix_tag, sp["shared_len"])
        return [{"prompt": np.concatenate(
                     [shared, rand(f"{tail_tag}/{i}", sp["tail_len"])]),
                 "max_new_tokens": sp["gen"]} for i in range(sp["n"])]

    eng = ServeEngine(model, params, EngineConfig(
        max_batch=sp["n"], prefill_chunk=16, page_size=sp["page_size"],
        max_seq_len=sp["shared_len"] + sp["tail_len"] + sp["gen"],
        prefix_cache=True))
    eng.run(wave("A", "a"))                       # warm-up (compile) wave
    cold = eng.run(wave("B", "b"))["stats"]       # fresh prefix: no hits
    warm = eng.run(wave("B", "c"))["stats"]       # same prefix, new tails
    assert cold["n_cached_tokens"] == 0, "cold wave unexpectedly hit"
    assert warm["n_cached_tokens"] > 0, "warm wave missed the cache"
    hit_rate = warm["n_cached_tokens"] / warm["n_prompt"]
    speedup = cold["ttft_p50_s"] / max(warm["ttft_p50_s"], 1e-9)
    return {
        "name": f"serve_engine/shared_prefix_{fmt}",
        "us_per_call": 1e6 / max(warm["tok_s"], 1e-9),
        "derived": (f"prefix_ttft_speedup={speedup:.2f}x,"
                    f"prefix_hit_rate={hit_rate:.3f},"
                    f"cold_ttft_p50_ms={cold['ttft_p50_s']*1e3:.1f},"
                    f"warm_ttft_p50_ms={warm['ttft_p50_s']*1e3:.1f},"
                    f"cold_ttft_p95_ms={cold['ttft_p95_s']*1e3:.1f},"
                    f"warm_ttft_p95_ms={warm['ttft_p95_s']*1e3:.1f},"
                    f"n_cached_tokens={warm['n_cached_tokens']},"
                    f"engine_tok_s={warm['tok_s']:.1f}")}


def _mixed_priority_row(model, params, fmt: str):
    """Priority/preemption lane: 6 batch-class requests saturate 4 slots,
    then 2 interactive requests arrive mid-run and preempt — the row
    records p50/p95 TTFT per class (measured from each request's arrival)
    and the preemption count."""
    import jax

    from repro.serve import api
    from repro.serve.engine import EngineConfig, ServeEngine

    vocab = model.cfg.vocab
    prompts = [np.asarray(jax.random.randint(
                   jax.random.fold_in(jax.random.PRNGKey(77), i), (8,),
                   0, vocab), np.int32) for i in range(8)]
    eng = ServeEngine(model, params, EngineConfig(
        max_batch=4, prefill_chunk=16, page_size=16, max_seq_len=48))

    def one_pass():
        finished = []
        preempt0 = eng.scheduler.n_preemptions
        t0 = time.perf_counter()
        for i in range(6):
            eng.submit(api.Request(prompt=prompts[i], max_new_tokens=24,
                                   priority="batch"))
        for _ in range(6):                 # batch requests get going
            finished.extend(eng.step())
        for i in range(6, 8):              # interactive arrivals preempt
            eng.submit(api.Request(prompt=prompts[i], max_new_tokens=8,
                                   priority="interactive"))
        while eng.scheduler.has_work():
            finished.extend(eng.step())
        s = eng._stats(finished, time.perf_counter() - t0)
        s["n_preemptions"] -= preempt0     # per-pass (the engine is reused)
        return s

    one_pass()                             # warm-up: compile both widths
    s = one_pass()
    by = {c: s["by_class"].get(c) for c in (0, 2)}
    parts = [f"engine_tok_s={s['tok_s']:.1f},n_preemptions={s['n_preemptions']}"]
    for c, label in ((0, "interactive"), (2, "batch")):
        cs = by[c]
        parts.append(f"{label}_ttft_p50_ms={cs['ttft_p50_s']*1e3:.1f},"
                     f"{label}_ttft_p95_ms={cs['ttft_p95_s']*1e3:.1f},"
                     f"{label}_latency_p50_ms={cs['latency_p50_s']*1e3:.1f}")
    return {"name": f"serve_engine/mixed_priority_{fmt}",
            "us_per_call": 1e6 / max(s["tok_s"], 1e-9),
            "derived": ",".join(parts)}


def _router_scale_row(model, params, fmt: str):
    """Replica-scaling lane: the same decode-heavy request mix through the
    router at 1 and 2 replicas (least-loaded dispatch). ``router_scale`` is
    the 2-replica / 1-replica aggregate tokens/s ratio of the same run —
    the number compression's smaller-model-more-replicas payoff rides on.
    Thread-replica scaling needs idle cores (the jitted step releases the
    GIL into XLA); ``n_cpus`` is recorded so the gate can account for
    single-core machines, where replicas time-slice one core."""
    import os

    from repro.serve.api import Request
    from repro.serve.engine import EngineConfig
    from repro.serve.router import Router

    rc = ROUTER
    reqs = [Request(prompt=p, max_new_tokens=g)
            for p, g in _requests([(rc["prompt_len"], rc["gen"])] * rc["n"],
                                  model.cfg.vocab)]
    cfg = EngineConfig(max_batch=rc["max_batch"], prefill_chunk=16,
                       page_size=rc["page_size"],
                       max_seq_len=rc["prompt_len"] + rc["gen"])
    tok = {}
    for n in (1, 2):
        router = Router.build(model, params, cfg, n, policy="least-loaded")
        router.serve(reqs)                  # warm-up: compile every replica
        tok[n] = max(router.serve(reqs)["stats"]["tok_s"]
                     for _ in range(2))     # best-of-2: shave OS noise
    scale = tok[2] / max(tok[1], 1e-9)
    return {"name": f"serve_engine/router_scale_{fmt}",
            "us_per_call": 1e6 / max(tok[2], 1e-9),
            "derived": (f"router_scale={scale:.2f}x,"
                        f"router_tok_s_1={tok[1]:.1f},"
                        f"router_tok_s_2={tok[2]:.1f},"
                        f"n_cpus={os.cpu_count() or 1}")}


def _router_affinity_row(model, params, fmt: str):
    """Prefix-affinity lane. Per policy (2-replica prefix vs round-robin
    control, plus the 1-replica reference): a warm-up wave compiles both
    replicas, ONE cold probe request caches the shared prefix on exactly
    one replica, then a warm wave of n same-prefix requests measures
    warm TTFT. Affinity routing sends the whole warm wave to the caching
    replica (hits); round-robin sprays it, and the half that lands cold
    re-prefills the prefix it just paid for. ``affinity_retention`` =
    2-replica-affinity warm-TTFT speedup / 1-replica warm-TTFT speedup —
    the fraction of the single-engine prefix-cache win that survives going
    multi-replica (same-run ratio, machine-corrected)."""
    import jax

    from repro.serve.api import Request
    from repro.serve.engine import EngineConfig
    from repro.serve.router import Router

    sp = SHARED_PREFIX
    vocab = model.cfg.vocab

    def rand(tag: str, n: int):
        key = jax.random.PRNGKey(abs(hash(tag)) % 2**31)
        return np.asarray(jax.random.randint(key, (n,), 0, vocab), np.int32)

    def wave(prefix_tag: str, tail_tag: str, n: int):
        shared = rand(prefix_tag, sp["shared_len"])
        return [Request(prompt=np.concatenate(
                    [shared, rand(f"{tail_tag}/{i}", sp["tail_len"])]),
                    max_new_tokens=sp["gen"]) for i in range(n)]

    cfg = EngineConfig(max_batch=sp["n"], prefill_chunk=16,
                       page_size=sp["page_size"],
                       max_seq_len=sp["shared_len"] + sp["tail_len"]
                       + sp["gen"], prefix_cache=True)

    def measure(n_replicas: int, policy: str):
        router = Router.build(model, params, cfg, n_replicas, policy=policy)
        # warm-up: fully distinct prompts spread over (and compile) all
        # replicas under every policy
        router.serve([Request(prompt=rand(f"W/{i}",
                                          sp["shared_len"] + sp["tail_len"]),
                              max_new_tokens=sp["gen"])
                      for i in range(2 * sp["n"])])
        cold = router.serve(wave("S", "cold", 1))["completions"]
        warm = router.serve(wave("S", "warm", sp["n"]))["completions"]
        hit = (sum(c.n_cached for c in warm)
               / max(sum(c.n_prompt for c in warm), 1))
        cold_ttft = cold[0].ttft_s
        warm_ttft = float(np.percentile([c.ttft_s for c in warm], 50))
        return cold_ttft / max(warm_ttft, 1e-9), hit, cold_ttft, warm_ttft

    single, _, _, _ = measure(1, "prefix")
    aff, aff_hit, cold_ttft, aff_warm = measure(2, "prefix")
    rr, rr_hit, _, rr_warm = measure(2, "round-robin")
    return {"name": f"serve_engine/router_affinity_{fmt}",
            "us_per_call": aff_warm * 1e6,
            "derived": (f"affinity_retention={aff/max(single,1e-9):.3f},"
                        f"affinity_ttft_speedup={aff:.2f}x,"
                        f"single_ttft_speedup={single:.2f}x,"
                        f"rr_ttft_speedup={rr:.2f}x,"
                        f"affinity_hit_rate={aff_hit:.3f},"
                        f"rr_hit_rate={rr_hit:.3f},"
                        f"cold_ttft_p50_ms={cold_ttft*1e3:.1f},"
                        f"affinity_warm_ttft_p50_ms={aff_warm*1e3:.1f},"
                        f"rr_warm_ttft_p50_ms={rr_warm*1e3:.1f}")}


def _router_failover_row(model, params, fmt: str):
    """Failure re-dispatch lane: 8 requests across 2 replicas, replica 0
    killed after it has streamed 6 tokens; its requests resume elsewhere
    (prompt + generated-so-far, reduced budget). ``failover_parity`` is 1
    iff every stitched token stream matches the sequential ``generate()``
    path exactly (greedy) — the router's correctness-under-failure gate."""
    import asyncio

    import jax

    from repro.serve.api import Request
    from repro.serve.engine import EngineConfig
    from repro.serve.router import Router
    from repro.serve.step import generate

    rc = ROUTER
    reqs = [Request(prompt=p, max_new_tokens=g)
            for p, g in _requests([(rc["prompt_len"], rc["gen"])] * 8,
                                  model.cfg.vocab)]
    cfg = EngineConfig(max_batch=4, prefill_chunk=16,
                       page_size=rc["page_size"],
                       max_seq_len=rc["prompt_len"] + rc["gen"])
    router = Router.build(model, params, cfg, 2, policy="least-loaded")
    router.serve(reqs)                      # warm-up: compile both replicas

    async def go():
        await router.start()
        futs = [await router.submit(r) for r in reqs]
        router.fail_replica_after(0, 6)
        comps = await asyncio.gather(*futs)
        await router.stop()
        return comps

    t0 = time.perf_counter()
    comps = asyncio.run(go())
    wall = time.perf_counter() - t0
    parity = 1
    for c, r in zip(sorted(comps, key=lambda c: c.request_id), reqs):
        ref = np.asarray(generate(model, params, r.prompt_ids[None, :],
                                  r.max_new_tokens))[0]
        if not np.array_equal(np.asarray(c.tokens), ref):
            parity = 0
    n_re = sum(c.n_redispatched for c in comps)
    tok_s = sum(c.n_generated for c in comps) / max(wall, 1e-9)
    return {"name": f"serve_engine/router_failover_{fmt}",
            "us_per_call": 1e6 / max(tok_s, 1e-9),
            "derived": (f"failover_parity={parity},"
                        f"n_redispatched={n_re},"
                        f"router_tok_s={tok_s:.1f}")}


def run():
    import jax

    from repro.models.model_zoo import build
    from repro.sparse.compress import (CompressionPlan, compress_params,
                                       prune_blocks_for_plan,
                                       quantize_compressed)

    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    # the reduced widths (d_model 64, d_ff 128) would be one (128, 128)
    # serving block per matrix, pruned whole; (8, 64) gives a real block grid
    # on the CPU (interpret/ref kernels take any block)
    plan = CompressionPlan(block=(8, 64), min_sparsity=0.5)
    pruned = prune_blocks_for_plan(params, plan, 0.85)
    cp = compress_params(pruned, plan)
    formats = {"dense": pruned, "bcsr": cp,
               "palette8": quantize_compressed(cp, bits=8)}
    # dense only on one mix (it is the reference point, not the product)
    cells = [(mix, fmt) for mix in MIXES for fmt in ("bcsr", "palette8")]
    cells.append(("mixed_len", "dense"))

    rows = []
    for mix_name, fmt in cells:
        requests = _requests(MIXES[mix_name], model.cfg.vocab)
        p = formats[fmt]
        s, eng = _engine_stats(model, p, requests)
        seq_tok_s = _sequential_tok_s(model, p, requests)
        rows.append(_row(f"serve_engine/{mix_name}_{fmt}", s, seq_tok_s, eng))

    # request-layer lanes: prefix caching (warm vs cold TTFT on the same
    # run) and priority preemption (per-class TTFT under slot contention)
    rows.append(_shared_prefix_row(model, formats["bcsr"], "bcsr"))
    rows.append(_mixed_priority_row(model, formats["bcsr"], "bcsr"))

    # router lanes (serve/router.py): replica scaling, prefix-affinity
    # retention vs the round-robin control, failure re-dispatch parity
    rows.append(_router_scale_row(model, formats["bcsr"], "bcsr"))
    rows.append(_router_affinity_row(model, formats["bcsr"], "bcsr"))
    rows.append(_router_failover_row(model, formats["bcsr"], "bcsr"))

    # recurrent archs under the engine (slot-state pools): BCSR-compressed,
    # decode-heavy mix — the --assert-speedup gate covers these rows too
    for arch in RECURRENT_ARCHS:
        rmodel = build(arch, reduced=True)
        rplan = CompressionPlan(block=(8, 64), min_sparsity=0.3,
                                min_size=4096)
        rpruned = prune_blocks_for_plan(rmodel.init(jax.random.PRNGKey(0)),
                                        rplan, 0.75)
        rcp = compress_params(rpruned, rplan)
        requests = _requests(MIXES["decode_heavy"], rmodel.cfg.vocab)
        s, eng = _engine_stats(rmodel, rcp, requests)
        seq_tok_s = _sequential_tok_s(rmodel, rcp, requests)
        rows.append(_row(f"serve_engine/{arch}_decode_heavy_bcsr",
                         s, seq_tok_s, eng))
    return rows


def _row(name, s, seq_tok_s, eng=None):
    derived = (f"engine_tok_s={s['tok_s']:.1f},"
               f"seq_tok_s={seq_tok_s:.1f},"
               f"batch_speedup={s['tok_s']/max(seq_tok_s,1e-9):.2f}x,"
               f"ttft_p50_ms={s['ttft_p50_s']*1e3:.1f},"
               f"ttft_p95_ms={s['ttft_p95_s']*1e3:.1f},"
               f"latency_p50_ms={s['latency_p50_s']*1e3:.1f},"
               f"latency_p95_ms={s['latency_p95_s']*1e3:.1f},"
               f"n_ticks={s['n_ticks']},"
               f"n_prefill_chunks={s['n_prefill_chunks']},"
               f"kv_pool_bytes={s['kv_page_bytes']},"
               f"state_pool_bytes={s['state_slot_bytes']}")
    row = {"name": name, "us_per_call": 1e6 / max(s["tok_s"], 1e-9)}
    if eng is not None:
        # registry-derived fields (whole engine lifetime: warm + timed
        # runs) + the full snapshot as row evidence
        occ = eng.metrics.get("repro_engine_page_occupancy")
        p95 = occ.percentile(95) if occ is not None else None
        derived += (f",page_occ_p95={-1.0 if p95 is None else p95:.1f}"
                    f",n_preemptions={eng.scheduler.n_preemptions}")
        row["metrics"] = eng.metrics.snapshot()
    row["derived"] = derived
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="",
                    help="write the rows to this path (BENCH json schema; "
                         "CI uploads it alongside BENCH_pr.json)")
    ap.add_argument("--assert-speedup", action="store_true",
                    help="exit nonzero unless the batched compressed engine "
                         "beats sequential compressed serving (aggregate "
                         "tokens/s) on every decode-dominated compressed "
                         "cell — attention AND recurrent (rwkv/"
                         "recurrentgemma) rows (prefill_heavy is reported "
                         "but not gated: a one-shot sequential prefill is "
                         "a single big dispatch and legitimately wins on "
                         "CPU)")
    ap.add_argument("--assert-from", default="",
                    help="apply --assert-speedup to rows loaded from this "
                         "previously written --json file instead of "
                         "re-running the benchmark — lets CI upload the "
                         "artifact first and gate afterwards, so a failed "
                         "gate still leaves the numbers to diagnose")
    args = ap.parse_args(argv)
    if args.assert_from:
        with open(args.assert_from) as f:
            rows = json.load(f)["rows"]
        args.assert_speedup = True
    else:
        rows = run()
        for r in rows:
            print(r)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"rows": rows}, f, indent=1)
            print(f"wrote {args.json}")
    if args.assert_speedup:
        import re

        bad = [r["name"] for r in rows
               if "dense" not in r["name"]
               and "prefill_heavy" not in r["name"]
               and "batch_speedup=" in r["derived"]
               and float(re.search(r"batch_speedup=([0-9.]+)x",
                                   r["derived"]).group(1)) <= 1.0]
        # shared-prefix lane: cache hits must actually happen AND cut TTFT
        for r in rows:
            if "prefix_ttft_speedup=" not in r["derived"]:
                continue
            spd = float(re.search(r"prefix_ttft_speedup=([0-9.]+)x",
                                  r["derived"]).group(1))
            hit = float(re.search(r"prefix_hit_rate=([0-9.]+)",
                                  r["derived"]).group(1))
            if spd <= 1.0 or hit <= 0.0:
                bad.append(f"{r['name']} (ttft speedup {spd}x, "
                           f"hit rate {hit})")
        for r in rows:
            d = r["derived"]
            # router scaling: 2 replicas must reach 1.6x aggregate tok/s on
            # a machine with cores to scale into (>= 8 — XLA's own intra-op
            # threads already eat part of a small core count); on 4-7 cores
            # the second replica must at least win (> 1.0), and below that
            # replicas are threads time-slicing one core, so only gate
            # against pathological overhead
            if "router_scale=" in d:
                scale = float(re.search(r"router_scale=([0-9.]+)x",
                                        d).group(1))
                n_cpus = int(re.search(r"n_cpus=(\d+)", d).group(1))
                floor = 1.6 if n_cpus >= 8 else (1.0 if n_cpus >= 4
                                                 else 0.5)
                if scale < floor:
                    bad.append(f"{r['name']} (scale {scale}x < {floor}x "
                               f"floor at {n_cpus} cpus)")
            # affinity routing must keep >= 80% of the single-replica warm-
            # TTFT speedup, and the round-robin control must show the gap
            # it would cost (sprayed warm wave -> cold prefills)
            if "affinity_retention=" in d:
                ret = float(re.search(r"affinity_retention=([0-9.]+)",
                                      d).group(1))
                ah = float(re.search(r"affinity_hit_rate=([0-9.]+)",
                                     d).group(1))
                rh = float(re.search(r"rr_hit_rate=([0-9.]+)", d).group(1))
                if ret < 0.8 or ah <= rh:
                    bad.append(f"{r['name']} (retention {ret}, hit rates "
                               f"affinity {ah} vs round-robin {rh})")
            # forced replica failure: >= 1 request re-dispatched and every
            # stitched token stream still matches generate()
            if "failover_parity=" in d:
                parity = int(re.search(r"failover_parity=(\d+)",
                                       d).group(1))
                n_re = int(re.search(r"n_redispatched=(\d+)", d).group(1))
                if parity != 1 or n_re < 1:
                    bad.append(f"{r['name']} (parity {parity}, "
                               f"{n_re} re-dispatched)")
        if bad:
            print(f"FAIL: batched engine did not beat sequential serving "
                  f"(or the prefix cache did not cut TTFT) on {bad}")
            return 1
        print("batched compressed engine > sequential on every "
              "decode-dominated compressed cell; prefix-cache hits cut "
              "warm TTFT below cold prefill; router lanes hold (replica "
              "scaling, affinity retention, failover parity)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
