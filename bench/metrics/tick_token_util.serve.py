"""Engine (serve/engine.py): tokens scheduled in the window's ticks over
the token positions those ticks computed (width x max_batch per tick),
from the engine's registry."""
from bench import readings


def read(ctx):
    run = ctx["run"]
    _, tokens = readings.hist_delta(run, "repro_engine_tick_tokens")
    ticks = readings.counter_delta_by(run, "repro_engine_ticks_total",
                                      "width")
    slots = sum(int(w) * n for w, n in ticks.items()) \
        * run.eng_cfg["max_batch"]
    return tokens / slots if slots else None
