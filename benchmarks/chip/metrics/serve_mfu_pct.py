"""Model step: operations the window's tokens needed (each prefill
token attending to the positions before it and the last one's logits,
each decoded token attending to its context and its logits), over the
window times the chips' bf16 peak."""

from benchmarks.chip import workcount


def read(ctx):
    steps = ctx.get("steps")
    if not steps:
        return None
    m = ctx["model"]
    flops = 0.0
    for st in steps:
        for n in st["prefill"]:
            flops += sum(workcount.lm_token_flops(m, p + 1, False)
                         for p in range(n))
            flops += 2.0 * m["d_model"] * m["vocab_size"]
        flops += sum(workcount.lm_token_flops(m, c, True)
                     for c in st["context"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return flops / (ctx["window_s"] * peak) * 100.0
