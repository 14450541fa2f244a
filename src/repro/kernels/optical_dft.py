"""Pallas TPU kernel: fused 4f-optics DFT pipeline (DFT-as-matmul + detector).

Hardware adaptation (DESIGN.md §3): the paper's accelerator computes a 2-D
Fourier transform by free-space diffraction.  On TPU the systolic MXU makes
the O(N^2) *matmul form* of the DFT the native equivalent:

    F = W_h @ A @ W_w^T,   W_n[j, k] = exp(-2 pi i j k / n) / sqrt(n)

Complex arithmetic is carried as separate (re, im) planes because the MXU
has no complex datapath.  The pipeline is two blocked complex matmuls with
the *physics fused in*:

  stage 1 (``dft_stage1``):  T = W_h @ quantize_dac(A)        (A real)
  stage 2 (``dft_stage2``):  I = |T @ W_w^T|^2                (detector)

Fusing the DAC quantizer into stage 1 and the square-law detector into
stage 2 keeps every intermediate in VMEM: HBM traffic is exactly one read
of A and one write of I (plus the small DFT factor matrices), vs 6 separate
HBM round-trips for the unfused op sequence.

Block shapes default to 128x128x128 (MXU-shaped); accumulation over the
contraction grid axis happens in fp32 VMEM scratch.  The contraction axis
is the *last* grid axis so TPU's sequential-grid guarantee makes the
accumulator carry valid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import INTERPRET, pick_block, program_name

__all__ = [
    "dft_matrix_factors",
    "dft_stage1",
    "dft_stage2",
    "dft_stage1_batched",
    "dft_stage2_batched",
    "optical_dft2_intensity",
    "optical_dft2_intensity_batched",
]


def dft_matrix_factors(n: int, dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """(re, im) of the unitary DFT matrix W_n (host-side, once per size)."""
    j = jnp.arange(n, dtype=jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    ang = -2.0 * jnp.pi * jnp.outer(j, j) / n
    scale = 1.0 / jnp.sqrt(jnp.asarray(n, ang.dtype))
    return (jnp.cos(ang) * scale).astype(dtype), (jnp.sin(ang) * scale).astype(dtype)


# --- stage 1: T = W @ quantize(A), A real ------------------------------------


def _stage1_kernel(wr_ref, wi_ref, a_ref, tr_ref, ti_ref, acc_r, acc_i,
                   *, levels: int, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    a = a_ref[...].astype(jnp.float32)
    if levels > 0:  # fused DAC quantization (SLM drive resolution)
        a = jnp.round(jnp.clip(a, 0.0, 1.0) * levels) / levels
    acc_r[...] += jnp.dot(wr_ref[...].astype(jnp.float32), a,
                          preferred_element_type=jnp.float32)
    acc_i[...] += jnp.dot(wi_ref[...].astype(jnp.float32), a,
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        tr_ref[...] = acc_r[...].astype(tr_ref.dtype)
        ti_ref[...] = acc_i[...].astype(ti_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dac_bits", "bm", "bk", "bn"))
def dft_stage1(wr: jax.Array, wi: jax.Array, a: jax.Array, *,
               dac_bits: int = 0, bm: int = 128, bk: int = 128, bn: int = 128):
    """T = W @ quantize_dac(A).  W: (m, k) complex as (wr, wi); A: (k, n) real."""
    m, kdim = wr.shape
    _, n = a.shape
    bm = pick_block(m, bm, 8)
    bk = pick_block(kdim, bk, 128)
    bn = pick_block(n, bn, 128)
    grid = (m // bm, n // bn, kdim // bk)
    levels = (1 << dac_bits) - 1 if dac_bits else 0
    kern = functools.partial(_stage1_kernel, levels=levels, nk=grid[2])
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # W re
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # W im
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # A
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=INTERPRET,
    )(wr, wi, a)


# --- stage 1, batched: T[b] = W @ quantize(A[b]) ------------------------------


def _stage1_batched_kernel(wr_ref, wi_ref, a_ref, tr_ref, ti_ref, acc_r, acc_i,
                           *, levels: int, nk: int, bb: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    wr = wr_ref[...].astype(jnp.float32)
    wi = wi_ref[...].astype(jnp.float32)
    for b in range(bb):  # bb frames share one load of the factor blocks
        a = a_ref[b].astype(jnp.float32)
        if levels > 0:  # fused DAC quantization (SLM drive resolution)
            a = jnp.round(jnp.clip(a, 0.0, 1.0) * levels) / levels
        acc_r[b] += jnp.dot(wr, a, preferred_element_type=jnp.float32)
        acc_i[b] += jnp.dot(wi, a, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        for b in range(bb):
            tr_ref[b] = acc_r[b].astype(tr_ref.dtype)
            ti_ref[b] = acc_i[b].astype(ti_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dac_bits", "bb", "bm", "bk",
                                             "bn"))
@program_name("dft_stage1_batched")
def dft_stage1_batched(wr: jax.Array, wi: jax.Array, a: jax.Array, *,
                       dac_bits: int = 0, bb: int = 1, bm: int = 128,
                       bk: int = 128, bn: int = 128):
    """T[b] = W @ quantize_dac(A[b]) for a whole batch in ONE kernel launch.

    W: (m, k) complex as (wr, wi); A: (batch, k, n) real.  The batch rides
    the *first* Pallas grid axis, so one ``pallas_call`` serves every frame
    and the per-shape factor matrices (wr, wi) are loaded once and reused
    across the batch — their BlockSpec index map ignores the batch index,
    which is exactly the aperture-packing story of the runtime's batched
    boundary crossing (K frames, one launch, shared optics).

    Block sizes are caller-driven (the runtime derives them from the VMEM
    budget — ``repro.runtime.tiling.choose_blocks``): ``bb`` frames ride
    each grid step and share one load of the W blocks, ``bm/bk/bn`` tile
    the matmul itself.
    """
    batch, kdim, n = a.shape
    m, _ = wr.shape
    bb = pick_block(batch, bb, 1)
    bm = pick_block(m, bm, 8)
    bk = pick_block(kdim, bk, 128)
    bn = pick_block(n, bn, 128)
    grid = (batch // bb, m // bm, n // bn, kdim // bk)
    levels = (1 << dac_bits) - 1 if dac_bits else 0
    kern = functools.partial(_stage1_batched_kernel, levels=levels,
                             nk=grid[3], bb=bb)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda b, i, j, k: (i, k)),      # W re
            pl.BlockSpec((bm, bk), lambda b, i, j, k: (i, k)),      # W im
            pl.BlockSpec((bb, bk, bn), lambda b, i, j, k: (b, k, j)),  # A
        ],
        out_specs=[
            pl.BlockSpec((bb, bm, bn), lambda b, i, j, k: (b, i, j)),
            pl.BlockSpec((bb, bm, bn), lambda b, i, j, k: (b, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, m, n), jnp.float32),
            jax.ShapeDtypeStruct((batch, m, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, bm, bn), jnp.float32),
            pltpu.VMEM((bb, bm, bn), jnp.float32),
        ],
        interpret=INTERPRET,
    )(wr, wi, a)


# --- stage 2: I = |T @ W^T|^2 --------------------------------------------------


def _stage2_kernel(tr_ref, ti_ref, wr_ref, wi_ref, out_ref, acc_r, acc_i, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    tr = tr_ref[...].astype(jnp.float32)
    ti = ti_ref[...].astype(jnp.float32)
    # W^T block: we load W[j_block, k_block] and contract its *rows*, i.e.
    # dot(t, w.T) — dimension_numbers keep the transpose inside the MXU pass.
    wr = wr_ref[...].astype(jnp.float32)
    wi = wi_ref[...].astype(jnp.float32)
    dot_t = lambda x, w: jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    acc_r[...] += dot_t(tr, wr) - dot_t(ti, wi)
    acc_i[...] += dot_t(tr, wi) + dot_t(ti, wr)

    @pl.when(k == nk - 1)
    def _detector():  # fused square-law camera
        out_ref[...] = (acc_r[...] ** 2 + acc_i[...] ** 2).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn"))
def dft_stage2(tr: jax.Array, ti: jax.Array, wr: jax.Array, wi: jax.Array, *,
               bm: int = 128, bk: int = 128, bn: int = 128):
    """I = |T @ W^T|^2.  T: (m, k) complex; W: (n, k) complex; I: (m, n)."""
    m, kdim = tr.shape
    n, _ = wr.shape
    bm = pick_block(m, bm, 8)
    bk = pick_block(kdim, bk, 128)
    bn = pick_block(n, bn, 128)
    grid = (m // bm, n // bn, kdim // bk)
    kern = functools.partial(_stage2_kernel, nk=grid[2])
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # T re
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # T im
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),   # W re (row-major)
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),   # W im
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=INTERPRET,
    )(tr, ti, wr, wi)


# --- stage 2, batched: I[b] = |T[b] @ W^T|^2 ----------------------------------


def _stage2_batched_kernel(tr_ref, ti_ref, wr_ref, wi_ref, out_ref,
                           acc_r, acc_i, *, nk: int, bb: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    wr = wr_ref[...].astype(jnp.float32)
    wi = wi_ref[...].astype(jnp.float32)
    dot_t = lambda x, w: jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    for b in range(bb):  # bb frames share one load of the factor blocks
        tr = tr_ref[b].astype(jnp.float32)
        ti = ti_ref[b].astype(jnp.float32)
        acc_r[b] += dot_t(tr, wr) - dot_t(ti, wi)
        acc_i[b] += dot_t(tr, wi) + dot_t(ti, wr)

    @pl.when(k == nk - 1)
    def _detector():  # fused square-law camera
        for b in range(bb):
            out_ref[b] = (acc_r[b] ** 2 + acc_i[b] ** 2).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "bm", "bk", "bn"))
@program_name("dft_stage2_batched")
def dft_stage2_batched(tr: jax.Array, ti: jax.Array, wr: jax.Array,
                       wi: jax.Array, *, bb: int = 1, bm: int = 128,
                       bk: int = 128, bn: int = 128):
    """I[b] = |T[b] @ W^T|^2 for a whole batch in ONE kernel launch.

    T: (batch, m, k) complex as (tr, ti); W: (n, k) complex; I: (batch, m, n).
    Like :func:`dft_stage1_batched`, the batch is the first grid axis, the
    W factor blocks are shared across it, and the block sizes (``bb``
    frames per grid step, ``bm/bk/bn`` matmul tiles) are caller-driven —
    the runtime derives them from the VMEM budget.
    """
    batch, m, kdim = tr.shape
    n, _ = wr.shape
    bb = pick_block(batch, bb, 1)
    bm = pick_block(m, bm, 8)
    bk = pick_block(kdim, bk, 128)
    bn = pick_block(n, bn, 128)
    grid = (batch // bb, m // bm, n // bn, kdim // bk)
    kern = functools.partial(_stage2_batched_kernel, nk=grid[3], bb=bb)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm, bk), lambda b, i, j, k: (b, i, k)),  # T re
            pl.BlockSpec((bb, bm, bk), lambda b, i, j, k: (b, i, k)),  # T im
            pl.BlockSpec((bn, bk), lambda b, i, j, k: (j, k)),        # W re
            pl.BlockSpec((bn, bk), lambda b, i, j, k: (j, k)),        # W im
        ],
        out_specs=pl.BlockSpec((bb, bm, bn), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bb, bm, bn), jnp.float32),
            pltpu.VMEM((bb, bm, bn), jnp.float32),
        ],
        interpret=INTERPRET,
    )(tr, ti, wr, wi)


def optical_dft2_intensity(a: jax.Array, *, dac_bits: int = 8,
                           block: int = 128) -> jax.Array:
    """Full fused pipeline: detector intensity of the 2-D unitary DFT of ``a``.

    Matches ``repro.core.optical`` with amplitude encoding, no noise, and no
    ADC quantization (the ADC is a separate global-auto-range pass — see
    ``repro.kernels.adc_dac``).
    """
    h, w = a.shape
    whr, whi = dft_matrix_factors(h)
    wwr, wwi = dft_matrix_factors(w)
    tr, ti = dft_stage1(whr, whi, a, dac_bits=dac_bits,
                        bm=block, bk=block, bn=block)
    return dft_stage2(tr, ti, wwr, wwi, bm=block, bk=block, bn=block)


@functools.partial(jax.jit, static_argnames=("dac_bits",))
def _dft2_intensity_batched_xla(a: jax.Array, *, dac_bits: int) -> jax.Array:
    """One fused batched XLA dispatch with the kernel pipeline's semantics:
    DAC quantize -> unitary 2-D DFT -> square-law detector, (b, h, w) in/out."""
    a = a.astype(jnp.float32)
    if dac_bits:
        levels = (1 << dac_bits) - 1
        a = jnp.round(jnp.clip(a, 0.0, 1.0) * levels) / levels
    f = jnp.fft.fft2(a.astype(jnp.complex64), norm="ortho")
    return jnp.abs(f) ** 2


def optical_dft2_intensity_batched(a: jax.Array, *, dac_bits: int = 8,
                                   block: int = 128, bb: int = 1,
                                   use_pallas: bool | None = None) -> jax.Array:
    """Batched fused pipeline: ``a`` is (batch, h, w), output (batch, h, w).

    On TPU this is two kernel launches total for the whole batch (vs
    2 * batch for a loop over :func:`optical_dft2_intensity`): the factor
    matrices are computed once per shape and every frame shares them via
    the batched grid axis.  Off-TPU, Pallas interpret mode is a
    *correctness* simulator — every grid step functionally updates the
    whole (batch, h, w) output buffer, so a batched interpret call copies
    batch-times more memory than the loop it replaces and inverts the perf
    story — so the same batched semantics execute as ONE fused XLA dispatch
    instead (``use_pallas`` overrides the automatic choice for tests).
    Either way the caller gets a single batched invocation per group.
    """
    if use_pallas is None:
        use_pallas = not INTERPRET
    if not use_pallas:
        return _dft2_intensity_batched_xla(a, dac_bits=dac_bits)
    _, h, w = a.shape
    whr, whi = dft_matrix_factors(h)
    wwr, wwi = dft_matrix_factors(w)
    tr, ti = dft_stage1_batched(whr, whi, a, dac_bits=dac_bits, bb=bb,
                                bm=block, bk=block, bn=block)
    return dft_stage2_batched(tr, ti, wwr, wwi, bb=bb, bm=block, bk=block,
                              bn=block)
