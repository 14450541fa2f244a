"""Kernels: the Pallas DFT kernels' share of their roofline.  Frames
retired in the window times the least time of one frame, over the device
time of the two Pallas DFT stages in the profile.  The ADC pass runs as
generic eager programs (``jit_clip``, ``jit_round``, ...) that the
fidelity shadow runs too, so it is left out until the program names it
apart.  A change that renames the stages leaves the metric silent until a
benchmark change repoints the table.
"""

from benchmarks.chip import workcount

FFT_PROGRAMS = ("jit_dft_stage1_batched", "jit_dft_stage2_batched")


def read(ctx):
    if ctx.get("category") != "fft":
        return None
    t = ctx["trace"].module_s(FFT_PROGRAMS)
    if t <= 0.0 or not ctx["frames"]:
        return None
    least = workcount.least_seconds(workcount.fft_frame(*ctx["frame_shape"]),
                                    ctx["peaks"])
    return ctx["frames"] * least / t * 100.0
