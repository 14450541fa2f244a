"""Executor host staging and dispatch: share of the traced window in
which the device idles under the program's ``stage`` spans (innermost),
mean over the chips."""

from benchmarks.chip.program_idle import idle_s


def read(ctx):
    idle = idle_s(ctx, "stage", "flush", "bench.flush")
    return None if idle is None else idle / ctx["trace"].window_s * 100.0
