"""Engine admission: median of the engine's ``queued`` spans, ``submit``
to the start of admission, of the requests admitted in the window."""

from benchmarks.chip.common import percentile


def read(ctx):
    waits = [s.t1 - s.t0 for s in ctx["spans"] if s.name == "queued"]
    return percentile(waits, 50) * 1e3 if waits else None
