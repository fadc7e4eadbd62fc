"""Model step (models/transformer.py): device self time per step of the
ops under the ``attention`` scope (norm, projections, attention, residual;
forward, recompute and backward), in ms, from the profiler trace
(bench/spans.py)."""
from bench import spans


def read(ctx):
    sp = spans.current()
    return sp.scope_ms_per_run().get("attention") if sp is not None else None
