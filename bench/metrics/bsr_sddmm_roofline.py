"""Kernels (kernels/bsr_sddmm): share of the roofline of the traced
training steps' weight-gradient calls, in percent."""
from bench import readings


def read(ctx):
    calls = readings.train_calls(ctx["run"], ctx["trace"])["bsr_sddmm"]
    return readings.roofline_pct(
        calls, ctx["trace"]["kernel_s"].get("bsr_sddmm"), ctx["peak"])
