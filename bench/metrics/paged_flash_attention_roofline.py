"""Kernels (kernels/paged_attention): share of the roofline of every
paged_flash_attention call in the window's ticks, from the device trace,
in percent."""
from bench import readings


def read(ctx):
    return readings.roofline_pct(
        readings.serve_attention_calls(ctx["run"]),
        ctx["trace"]["kernel_s"].get("paged_flash_attention"), ctx["peak"])
