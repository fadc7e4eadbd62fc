"""Kernels (kernels/bsr_spmm): share of the roofline of every bsr_spmm
call in the window's ticks, from the device trace, in percent."""
from bench import readings


def read(ctx):
    return readings.roofline_pct(readings.serve_spmm_calls(ctx["run"]),
                                 ctx["trace"]["kernel_s"].get("bsr_spmm"),
                                 ctx["peak"])
