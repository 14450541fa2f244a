"""Engine sampling: milliseconds a decode step in which the device idles
under the engine's ``engine.sample`` spans (the positions' fetch, the
per-lane argmaxes), over the window's decode steps (its
``engine.decode`` spans, one a step, as the ``decode_steps`` counter)."""

from benchmarks.chip.program_idle import idle_s


def read(ctx):
    steps = sum(1 for s in ctx["spans"] if s.name == "engine.decode")
    idle = idle_s(ctx, "engine.sample", "engine.step", "bench.step")
    return None if idle is None or not steps else idle / steps * 1e3
