"""Device (one v5e): device idle time per tick that falls, on the host's
clock after alignment, inside the engine's host phases (``engine.schedule``,
``prepare``, ``dispatch``, ``bookkeep``), in ms, from the profiler trace
(bench/spans.py)."""
from bench import spans


def read(ctx):
    sp = spans.current()
    ticks = sp.ticks() if sp is not None else []
    if not ticks or sp.offset_ns is None:
        return None
    idle = sp.idle_by_span()
    return sum(idle.get(p, 0) for p in spans.HOST_PHASES) * 1e-6 / len(ticks)
