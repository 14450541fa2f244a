"""Expert layer: (token, held expert) pairs a decode step computed, per
expert layer and held expert, from the ``expert_tokens`` of the window's
``engine.decode`` spans."""

from benchmarks.chip import workcount_moe


def read(ctx):
    pairs = [s.attrs["expert_tokens"] for s in ctx["spans"]
             if s.name == "engine.decode" and "expert_tokens" in s.attrs]
    if not pairs:
        return None
    m = ctx["model"]
    per_step = workcount_moe.moe_layers(m) * workcount_moe.held_experts(m)
    return sum(pairs) / (len(pairs) * per_step)
