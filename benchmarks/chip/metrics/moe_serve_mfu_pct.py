"""Model step of a sparse-expert model: operations the window's tokens
needed (each prefill token attending to the positions up to its own on
up-projected K and V, and the last one's logits; each decoded token
attending to its context in the latent space and its logits; each
(token, held expert) pair the ``engine.prefill`` and ``engine.decode``
spans report as ``expert_tokens``), over the window times the chips'
bf16 peak.  Those pairs are the ones the layer computed, so they include
the pairs of free decode lanes and of prefill padding, which no request
needed."""

from benchmarks.chip import workcount_moe


def read(ctx):
    steps = ctx.get("steps")
    pairs = [s.attrs["expert_tokens"] for s in ctx["spans"]
             if s.name in ("engine.prefill", "engine.decode")
             and "expert_tokens" in s.attrs]
    if not steps or not pairs:
        return None
    m = ctx["model"]
    flops = sum(pairs) * workcount_moe.expert_pair_flops(m)
    for st in steps:
        flops += sum(workcount_moe.prefill_flops(m, n) for n in st["prefill"])
        flops += sum(workcount_moe.token_flops(m, c, True, True)
                     for c in st["context"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return flops / (ctx["window_s"] * peak) * 100.0
