"""One run of one cell: set-up, window, readings, check, result line."""
from __future__ import annotations

import gc
import importlib.util
import shutil
import sys
import time

from bench import trace as trace_lib


def _metric_reader(name: str):
    """The reader module of a per-layer metric, found by its name."""
    from bench.run import BENCH
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _device_block(devices, kind: str) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(spec, cell, cfg, mix, args, devices, peak, counter, t_start,
        out_dir) -> dict:
    from bench.run import metrics_of
    import jax

    kind = mix["kind"]
    if kind in ("open_poisson", "closed_sessions"):
        from bench.serve import ServeRun
        r = ServeRun(cfg, mix, args.seed, args.seconds)
    elif kind == "train_stream":
        from bench.train import TrainRun
        r = TrainRun(cfg, mix, args.seed, args.seconds)
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    r.setup()

    trace_dir = out_dir / "trace" / cell["name"]
    opened = {}

    def on_open():
        opened["setup_s"] = time.perf_counter() - t_start
        counter.open = True
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            jax.profiler.start_trace(str(trace_dir))

    t0, t1 = r.window(on_open)
    counter.open = False
    if args.trace:
        jax.profiler.stop_trace()
    print(f"bench: setup_s {opened['setup_s']:.3f}, compilations before the "
          f"window {counter.total - counter.in_window}, inside the window "
          f"{counter.in_window}", file=sys.stderr)
    device = _device_block(devices, devices[0].device_kind)
    e2e = r.end_to_end()
    e2e["setup_s"] = opened["setup_s"]
    print("bench: window " + " ".join(f"{k} {v!r}" for k, v in e2e.items()),
          file=sys.stderr)

    wanted = metrics_of(spec, cell, bool(args.trace))
    metrics = {}
    breakdown = None
    if args.trace:
        red = trace_lib.reduce_dir(trace_dir, t0, t1)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        ctx = {"run": r, "trace": red, "peak": peak, "cfg": cfg, "mix": mix}
        for m in wanted:
            v = _metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in wanted:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    attempted, failed = r.counts()
    checks = r.check()
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    gc.collect()
    return result
