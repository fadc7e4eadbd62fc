"""Model step (models/transformer.py, train/step.py): device self time per
step of the ops under the ``head`` and ``loss`` scopes (final norm, tied
head matmul, log-softmax and z-loss; forward and backward), in ms, from
the profiler trace (bench/spans.py)."""
from bench import spans


def read(ctx):
    sp = spans.current()
    times = sp.scope_ms_per_run() if sp is not None else {}
    if "head" not in times and "loss" not in times:
        return None
    return times.get("head", 0.0) + times.get("loss", 0.0)
