"""Kernels (kernels/bsr_spmm): share of the roofline of the traced
training steps' bsr_spmm calls (forward and input gradient), in percent."""
from bench import readings


def read(ctx):
    calls = readings.train_calls(ctx["run"], ctx["trace"])["bsr_spmm"]
    return readings.roofline_pct(
        calls, ctx["trace"]["kernel_s"].get("bsr_spmm"), ctx["peak"])
