"""Model step (models/transformer.paged_step): the model's work in the
window's ticks (the architecture's ``serve_tick_flops``, bench/arch/) over
window x the chip's peak, in percent."""


def read(ctx):
    run = ctx["run"]
    stored = run.stored_weights()
    flops = sum(run.arch.serve_tick_flops(run.cfg, start, n, stored)
                for _, start, n in run.plans)
    window = ctx["trace"]["window_s"]
    if not flops or not window:
        return None
    return 100.0 * flops / (window * ctx["peak"]["bf16_flops"])
