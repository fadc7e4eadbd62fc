"""KV manager (serve/paged_kv.py): mean over the window's ticks of the
allocated KV pages (the engine's ``repro_engine_page_occupancy``) over the
pages the slots could hold (max_batch x pages_per_slot)."""
from bench import readings


def read(ctx):
    run = ctx["run"]
    n, total = readings.hist_delta(run, "repro_engine_page_occupancy")
    if n == 0:
        return None
    e = run.eng_cfg
    pages = e["max_batch"] * -(-e["max_seq_len"] // e["page_size"])
    return total / n / pages
