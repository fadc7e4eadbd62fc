"""Model step (train/step.py): the model's work in the traced steps
(bench/work/model.py: 6 x stored weights and head per token, causal
attention, no recompute) over the traced window x the chip's peak, in
percent."""
from bench import weights as W
from bench.work import model


def read(ctx):
    run = ctx["run"]
    s = W.sizes(run.cfg)
    per_step = model.train_step(int(run.mix["batch"]),
                                int(run.mix["seq_len"]),
                                run.stored_weights(), s["V"] * s["d"],
                                s["L"], s["H"], s["hd"])
    window = ctx["trace"]["window_s"]
    if not run.done_times or not window:
        return None
    return 100.0 * per_step * len(run.done_times) / (
        window * ctx["peak"]["bf16_flops"])
