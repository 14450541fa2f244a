"""Executor batching and tiling: dispatches (invocations) per frame of
the window, from the telemetry counters."""


def read(ctx):
    return ctx["invocations"] / ctx["calls"] if ctx["calls"] else None
