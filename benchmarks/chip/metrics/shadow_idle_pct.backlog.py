"""Fidelity shadow: share of the traced window in which the device idles
under the program's ``fidelity-shadow`` spans (innermost), mean over the
chips; silent where the executor runs no shadow."""

from benchmarks.chip.program_idle import idle_s


def read(ctx):
    idle = idle_s(ctx, "fidelity-shadow", "flush", "bench.flush")
    return None if idle is None else idle / ctx["trace"].window_s * 100.0
