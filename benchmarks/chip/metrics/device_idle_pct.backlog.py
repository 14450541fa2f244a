"""Device: share of the traced window with no program running, mean over
the chips (profiler trace)."""

from benchmarks.chip.layers import idle_pct


def read(ctx):
    return idle_pct(ctx)
