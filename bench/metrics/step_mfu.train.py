"""Model step (train/step.py): the model's work in the traced steps (the
architecture's ``train_step_flops``, bench/arch/: 6 x stored weights and
head per token, attention, no recompute) over the traced window x the
chip's peak, in percent."""


def read(ctx):
    run = ctx["run"]
    per_step = run.arch.train_step_flops(run.cfg, int(run.mix["batch"]),
                                         int(run.mix["seq_len"]),
                                         run.stored_weights())
    window = ctx["trace"]["window_s"]
    if not run.done_times or not window:
        return None
    return 100.0 * per_step * len(run.done_times) / (
        window * ctx["peak"]["bf16_flops"])
