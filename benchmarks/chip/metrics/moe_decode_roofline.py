"""Decode step program of a sparse-expert model: the least bytes of the
window's decode steps (the stored weights but the embedding and the
routed experts, the held experts each step reached, by the
``experts_reached`` of its ``engine.decode`` span, and the latent cache
of each live lane's positions) over HBM bandwidth, against the device
time of the decode program (``jit_decode_step``) in the profile."""

from benchmarks.chip import workcount_moe

DECODE_PROGRAMS = ("jit_decode_step",)


def read(ctx):
    steps = ctx.get("steps")
    reached = [s.attrs["experts_reached"] for s in ctx["spans"]
               if s.name == "engine.decode" and "experts_reached" in s.attrs]
    if not steps or not reached:
        return None
    t = ctx["trace"].module_s(DECODE_PROGRAMS)
    if t <= 0.0:
        return None
    m = ctx["model"]
    least = sum(workcount_moe.decode_step_bytes(m, sum(st["context"]), 0)
                for st in steps if st["context"])
    least += sum(reached) * workcount_moe.expert_bytes(m)
    return least / ctx["peaks"]["hbm_bytes_per_s"] / t * 100.0
