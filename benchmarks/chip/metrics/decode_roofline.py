"""Decode step program: the least bytes of the window's decode steps (the
stored block and head weights once a step, K and V of each live lane's
positions) over HBM bandwidth, against the device time of the decode
program (``jit_decode_step``) in the profile."""

from benchmarks.chip import workcount

DECODE_PROGRAMS = ("jit_decode_step",)


def read(ctx):
    steps = ctx.get("steps")
    if not steps:
        return None
    t = ctx["trace"].module_s(DECODE_PROGRAMS)
    if t <= 0.0:
        return None
    m = ctx["model"]
    least = sum(workcount.lm_decode_step_bytes(m, sum(st["context"]))
                for st in steps if st["context"])
    return least / ctx["peaks"]["hbm_bytes_per_s"] / t * 100.0
