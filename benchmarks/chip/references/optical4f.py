"""Plain float64 reference of the 4f offload's conversion boundary, on the
host with numpy.  It imports nothing of the program.

``fft``: DAC quantization, the unitary 2-D DFT, the square-law detector
and the auto-ranged ADC.

``bits`` is ``(dac_bits, adc_bits)``: the configuration's converters,
or lower ones for the control.
"""

from __future__ import annotations

import numpy as np


def quantize(x, bits: int):
    levels = (1 << bits) - 1
    return np.round(np.clip(x, 0.0, 1.0) * levels) / levels


def fft_reference(frame, bits: tuple[int, int]):
    """DAC quantize, unitary 2-D DFT, square-law detector, auto-ranged ADC."""
    dac, adc = bits
    a = quantize(np.asarray(frame, np.float64), dac)
    intensity = np.abs(np.fft.fft2(a, norm="ortho")) ** 2
    scale = max(float(intensity.max()), 1e-20)
    return quantize(intensity / scale, adc) * scale


def adc_code_gap(got, ref, adc_bits: int) -> int:
    """Worst ADC code difference against the reference, each frame read
    against its own full scale."""
    levels = (1 << adc_bits) - 1
    got = np.asarray(got, np.float64)
    codes = np.round(got / max(got.max(), 1e-30) * levels)
    ref_codes = np.round(ref / max(ref.max(), 1e-30) * levels)
    return int(np.abs(codes - ref_codes).max())
