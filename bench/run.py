"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration under
``bench/configs/``, the configuration's architecture under ``bench/arch/``
and its traffic mix under ``bench/traffic/`` by name, builds the program's
system under test from them and the seed (set-up), measures for
``--seconds`` (the window), checks what the window produced against the
plain reference, and prints one JSON line last on standard output.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and reports its per-layer metrics.

Runs only on a TPU: with no accelerator, or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
OUT_DIR = CHECKOUT / ".bench_out"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, workload entry, configuration, traffic mix). A
    configuration whose architecture has no module under ``bench/arch/``
    exits here, before any device work."""
    spec = load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(CHECKOUT / confs[cell["config"]]["file"])
    from bench import arch
    arch.of(cfg)
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, cfg, mix


def metrics_of(spec: dict, cell: dict, trace: bool) -> list[dict]:
    """The metric entries this cell reports in this kind of run."""
    name = cell["name"]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    have = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in have)]


def accelerator(chips: int, peaks: dict):
    """The devices the cell runs on; exits non-zero without a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, found platform {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"bench: cell needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips], kind


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however small or quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations, and those inside the window."""

    def __init__(self):
        import jax
        self.total = 0
        self.in_window = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1
            self.in_window += int(self.open)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be >= 0")
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(CHECKOUT))
    spec, cell, cfg, mix = load_cell(args.workload)
    from bench import cell as cell_lib
    peaks = load_json(BENCH / "peaks.json")
    enable_compile_cache()
    devices, kind = accelerator(int(cell["chips"]), peaks)
    counter = CompileCounter()
    result = cell_lib.run(spec, cell, cfg, mix, args, devices, peaks[kind],
                          counter, T_START, OUT_DIR)
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
