"""Whole offload step: frames retired in the window times the least time
of one frame, over the window times the chips used (the share of the
chips' peak the retired work needed)."""

from benchmarks.chip import workcount


def read(ctx):
    if ctx.get("category") != "fft" or not ctx["frames"]:
        return None
    least = workcount.least_seconds(workcount.fft_frame(*ctx["frame_shape"]),
                                    ctx["peaks"])
    return ctx["frames"] * least / (ctx["window_s"] * ctx["chips"]) * 100.0
