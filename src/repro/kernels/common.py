"""Shared Pallas kernel utilities.

Kernels are written for TPU (explicit BlockSpec VMEM tiling, MXU-aligned
block shapes) and validated on CPU with ``interpret=True``, which executes
the kernel body in Python.  ``INTERPRET`` flips automatically.
"""

from __future__ import annotations

import jax

__all__ = ["INTERPRET", "MXU", "LANE", "SUBLANE", "round_up", "pick_block",
           "program_name"]

INTERPRET = jax.default_backend() != "tpu"

# TPU v5e geometry: 128x128 MXU systolic array; (8, 128) float32 VREG tiles.
MXU = 128
LANE = 128
SUBLANE = 8


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pick_block(dim: int, preferred: int, align: int) -> int:
    """Largest multiple of ``align`` <= max(preferred, align) that divides
    ``dim``; falls back to ``dim`` itself.

    A TPU block's last two dimensions must be multiples of (8, 128) or span
    the whole array, so the block never drops below ``align``: a preferred
    size under it rounds up to ``align``, and a dimension no aligned block
    divides is taken whole (deliberately tiny test shapes).
    """
    b = min(max(preferred, align) // align * align, dim // align * align)
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim


def program_name(name: str):
    """Decorator: ``jax.jit`` of the decorated function makes a program
    named ``jit_<name>``, whatever the function itself is called.  Profiles
    list device time by program name, and the benchmark's roofline tables
    find their programs by it, so a rename must not change it silently."""
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return wrap
