"""Model step (models/transformer.paged_step): the model's work in the
window's ticks (bench/work/model.py) over window x the chip's peak, in
percent."""
from bench import weights as W
from bench.work import model


def read(ctx):
    run = ctx["run"]
    s = W.sizes(run.cfg)
    stored = run.stored_weights()
    flops = sum(model.serve_tick(start, n, stored, s["V"] * s["d"], s["L"],
                                 s["H"], s["hd"])
                for _, start, n in run.plans)
    window = ctx["trace"]["window_s"]
    if not flops or not window:
        return None
    return 100.0 * flops / (window * ctx["peak"]["bf16_flops"])
