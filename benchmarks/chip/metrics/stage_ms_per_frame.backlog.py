"""Executor host staging and dispatch: milliseconds of the program's
``stage`` spans per frame of the window."""

from benchmarks.chip.layers import per_frame_ms


def read(ctx):
    return per_frame_ms(ctx, "stage")
