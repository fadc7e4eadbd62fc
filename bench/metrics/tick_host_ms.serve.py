"""Engine (serve/engine.py): mean over the window's ticks of the host's
time in a tick outside its wait for the device (``engine.tick`` less
``engine.sync``), in ms, from the profiler trace (bench/spans.py)."""
from bench import spans


def read(ctx):
    sp = spans.current()
    ticks = sp.ticks() if sp is not None else []
    if not ticks:
        return None
    host_ns = sum(t["end"] - t["start"] - t["phases"].get(spans.SYNC, 0)
                  for t in ticks)
    return host_ns * 1e-6 / len(ticks)
