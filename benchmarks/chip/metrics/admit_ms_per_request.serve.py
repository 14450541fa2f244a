"""Engine admission: milliseconds of the engine's ``engine.admit`` spans
(prefill, cache splice, first-token argmax) per request admitted in the
window (one span each, as the ``admitted`` counter)."""

from benchmarks.chip.layers import span_s


def read(ctx):
    n = sum(1 for s in ctx["spans"] if s.name == "engine.admit")
    return span_s(ctx, "engine.admit") / n * 1e3 if n else None
