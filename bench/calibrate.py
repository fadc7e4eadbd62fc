"""Readings that set a cell's correctness limits (run on the chip, by hand).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 30]

For every seed it builds the cell as a run does and prints one JSON line
with the number the run compares, read from the program (the lower
reading). On the control seeds it also reads the control: the reference
one precision below the configuration's (float8 for bfloat16) put in the
program's place. A training cell also reads the planted fault of half the
batch left out. A serving cell drives the window at the cell's own load,
so its sample holds as many served tokens as a run's. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

from bench.run import enable_compile_cache, load_cell  # noqa: E402


def serve_seed(cfg, mix, seed, seconds, control):
    from bench import check, loadgen
    from bench.serve import ServeRun
    r = ServeRun(cfg, mix, seed, seconds)
    r.setup()
    r.window()
    served = r.served()
    e2e = r.end_to_end()
    r.engine = r.params = None
    gc.collect()
    picked = check.sample_requests(
        served, seed, None if mix["check"].get("all_served")
        else int(mix["check"]["served_tokens"]))
    got = check.logit_gaps(cfg, seed, served, picked,
                           r.eng_cfg["max_seq_len"],
                           loadgen.max_len(mix["output_tokens"]),
                           control=control)
    return dict(got, output_tok_s=e2e["output_tok_s"],
                ttft_p90_ms=e2e.get("ttft_p90_ms"),
                token_gap_p95_ms=e2e["token_gap_p95_ms"])


def train_seed(cfg, mix, seed, control, fault):
    from bench import check
    from bench.train import TrainRun

    class HalfBatch(TrainRun):
        """The planted fault: half of each batch left out, the loss's mean
        taken over the rest."""
        def _batch(self):
            b = super()._batch()
            return {k: v[: len(v) // 2] for k, v in b.items()}

    def program(cls):
        r = cls(cfg, mix, seed, 0)
        r.setup()
        r.state = r.step = None
        gc.collect()
        return r

    r = program(TrainRun)
    batches = r.check_batches()
    ref = check.reference_steps(cfg, mix, seed, batches)
    out = {"sound": check.train_gaps(
        r.losses, check.program_leaves(r.leaves, r.grad1, r.change,
                                       ref["shapes"]), ref)}
    if control:
        ctl = check.reference_steps(cfg, mix, seed, batches, quant="fp8")
        out["control"] = check.train_gaps(
            ctl["losses"], {k: v for k, v in ctl["leaves"].items()
                            if k in ref["leaves"]}, ref)
    if fault:
        f = program(HalfBatch)
        out["half_batch"] = check.train_gaps(
            f.losses, check.program_leaves(f.leaves, f.grad1, f.change,
                                           ref["shapes"]), ref)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=30)
    args = ap.parse_args()
    _, cell, cfg, mix = load_cell(args.workload)
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if mix["kind"] == "train_stream":
            got = train_seed(cfg, mix, seed, seed in controls,
                             seed in controls)
        else:
            got = serve_seed(cfg, mix, seed, args.seconds, seed in controls)
        print(json.dumps({"seed": seed, "s": time.perf_counter() - t,
                          **got}), flush=True)


if __name__ == "__main__":
    main()
