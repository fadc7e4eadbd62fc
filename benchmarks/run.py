"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Roofline terms for the big
architectures come from the dry-run artifacts (launch/dryrun.py) and are
appended when experiments/dryrun/ exists.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

MODULES = [
    "benchmarks.optimizer_variance",      # paper Fig. 5
    "benchmarks.compression_sweep",       # paper Fig. 6 + Table 1
    "benchmarks.retraining",              # paper Fig. 7
    "benchmarks.mm_comparison",           # paper Table 2 + Fig. 8
    "benchmarks.layerwise_compression",   # paper Tables A1-A4
    "benchmarks.inference_speedup",       # paper Table 3
    "benchmarks.kernel_bench",            # kernels
]


def dryrun_rows(root="experiments/dryrun"):
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "*.json"))):
        r = json.load(open(path))
        if not r.get("ok"):
            rows.append({"name": f"dryrun/{r['cell']}", "us_per_call": 0.0,
                         "derived": f"FAILED:{r.get('error', '')[:80]}"})
            continue
        roof = r["roofline"]
        rows.append({
            "name": f"dryrun/{r['cell']}",
            "us_per_call": roof["bound_s"] * 1e6,
            "derived": (f"dominant={roof['dominant']},"
                        f"compute_s={roof['compute_s']:.4f},"
                        f"memory_s={roof['memory_s']:.4f},"
                        f"collective_s={roof['collective_s']:.4f},"
                        f"useful={roof['useful_flops_ratio']:.3f},"
                        f"mem_gb={r['memory']['peak_per_device_gb']:.2f}"),
        })
    return rows


def main() -> None:
    import importlib
    print("name,us_per_call,derived")
    t_all = time.time()
    failed = []
    for modname in MODULES:
        try:
            mod = importlib.import_module(modname)
            for row in mod.run():
                print(f"{row['name']},{row['us_per_call']:.2f},"
                      f"\"{row['derived']}\"")
                sys.stdout.flush()
        except Exception as e:  # noqa: BLE001
            print(f"{modname},0,\"ERROR:{type(e).__name__}:{e}\"")
            failed.append(modname)
    for row in dryrun_rows():
        print(f"{row['name']},{row['us_per_call']:.2f},\"{row['derived']}\"")
    print(f"# total wall time: {time.time()-t_all:.1f}s")
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
