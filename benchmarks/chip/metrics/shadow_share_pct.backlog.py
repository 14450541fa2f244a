"""Fidelity shadow: share of the window in the program's
``fidelity-shadow`` spans; silent where the executor runs no shadow."""

from benchmarks.chip.layers import window_pct


def read(ctx):
    if not any(s.name == "fidelity-shadow" for s in ctx["spans"]):
        return None
    return window_pct(ctx, "fidelity-shadow")
