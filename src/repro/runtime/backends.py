"""Backend registry: three interchangeable executors per op category.

Every backend implements the same three op categories the planner knows
about (``fft``, ``conv``, ``matmul``) with identical call signatures, so
the executor can swap them per the routing table without touching callers:

  ``host``        pure digital JAX (fft2 / circular conv / matmul) — the
                  baseline the planner's ``host_s`` measures.
  ``optical-sim`` the simulated analog engine with the conversion boundary
                  applied: the fused Pallas DFT pipeline (DAC quantization
                  folded into stage 1, square-law detector into stage 2)
                  plus the auto-ranged ADC read path for ``fft``; the 4f
                  physics simulator for ``conv``; DAC->MVM->ADC for
                  ``matmul``.  Returns a modeled :class:`StepCost` built
                  from the executor's accelerator spec so every result is
                  priced, not just produced.
  ``ideal``       the zero-conversion-cost analog bound (paper Table 1):
                  exact digital values, cost = analog physics only.

Op semantics (fixed across backends so results are comparable):

  fft(a)        -> detector intensity |F a|^2 of the unitary 2-D DFT,
                   a real, values in [0, 1] (the camera cannot see phase;
                   a single capture yields intensity — paper App. A.1).
  conv(a, k)    -> circular 2-D convolution (4-step interferometric capture
                   + host-side inverse transform, paper Eq. 1).
  matmul(a, w)  -> a @ w with activations streamed through the converters
                   (weights held in the optical domain, amortized).

Batching is *real* on every backend: ``run`` stacks the group's same-shape
items into one ``(K, H, W)`` array and makes ONE batched invocation — a
single jitted ``fft2``/conv/matmul on the host, the batched Pallas DFT
pipeline (batch as the leading grid axis, factor matrices shared across
frames) or a vmapped 4f/MVM simulation on the analog backends — so a
K-deep flush pays one dispatch round-trip and one kernel launch instead
of K.  Per-item semantics are preserved inside the batch (per-frame ADC
auto-ranging, per-item affine range mapping, per-item matmul scaling), so
batched results match a Python loop of single-item calls to float
tolerance (the only difference is reduction/blocking order inside XLA).
"""

from __future__ import annotations

import abc
import dataclasses
import functools
import hashlib
import math
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accelerator import (
    OpticalFourierAcceleratorSpec,
    OpticalMVMAcceleratorSpec,
    StepCost,
)
from repro.core.optical import (
    OpticalSimParams,
    adc_quantize,
    adc_quantize_batched,
    dac_quantize,
    fourier_mask_for_kernel,
    optical_conv2d_batched,
)
from repro.kernels.common import INTERPRET
from repro.kernels.optical_dft import (
    _dft2_intensity_batched_xla,
    dft_matrix_factors,
    dft_stage1_batched,
    dft_stage2_batched,
)
from repro.runtime.residency import residency_key
from repro.runtime.telemetry import RuntimeTelemetry
from repro.runtime.tiling import BlockPlan, MemoryBudget, choose_blocks

__all__ = [
    "CATEGORIES",
    "CONV_CAPTURES",
    "BackendContext",
    "ExecutionBackend",
    "HostBackend",
    "OpticalSimBackend",
    "IdealBackend",
    "conv_range_map",
    "ideal_step_cost",
    "register_backend",
    "get_backend",
    "available_backends",
    "stage_group",
]

CATEGORIES = ("fft", "conv", "matmul")

# Interferometric complex recovery (needed by conv) costs 4 captures.
CONV_CAPTURES = 4


@dataclasses.dataclass
class BackendContext:
    """Per-executor state shared with backends: the accelerator spec plus
    the shape-keyed caches (DFT factor matrices, Fourier-plane masks,
    resolved Pallas block plans).  Compiled kernels are cached by jit
    itself, keyed on the same shapes *and* block sizes (the block sizes
    are jit-static), so a replanned layout always compiles fresh.

    ``pipeline_depth`` is how deep the owning executor overlaps boundary
    crossings for *this* invocation; analog backends thread it into
    ``batched_step_cost`` so the modeled price matches how the invocation
    is actually overlapped (2 = the executor's async double-buffered
    flush; 1 = strictly serial crossings).  The executor writes it
    per-dispatch (and ``warm()`` mirrors the same write) from the
    dispatched category's per-engine pipeline window
    (``set_pipeline_window``), falling back to the global
    ``pipeline_depth`` for unpinned categories — so a backend never needs
    to know which window it ran under, only the depth it was given.

    ``n_devices`` is how many replicated simulated accelerators the sharded
    backend scatters one invocation across (the executor writes the
    per-category effective count here before every dispatch — and before
    ``warm`` — so sharded dispatch shapes are primed consistently);
    ``shard_mode`` picks between group sharding, frame sharding, and the
    automatic policy (see ``repro.runtime.sharded``).

    ``mem_budget`` is the per-device staging byte budget
    (``repro.runtime.tiling.MemoryBudget``): the memory flush-group stacks
    are allocated in, HBM on TPU.  The executor tiles flush groups against
    it.  The batched Pallas grid's block sizes (``blocks_for``) are sized
    against ``block_budget`` instead, the VMEM one grid step lives in."""

    spec: OpticalFourierAcceleratorSpec | OpticalMVMAcceleratorSpec
    factor_cache: dict[tuple, tuple[jax.Array, jax.Array]] = \
        dataclasses.field(default_factory=dict)
    mask_cache: dict[tuple, jax.Array] = dataclasses.field(default_factory=dict)
    pipeline_depth: int = 2
    n_devices: int = 1
    shard_mode: str = "auto"
    mem_budget: "MemoryBudget | None" = None
    block_cache: dict[tuple, "BlockPlan"] = \
        dataclasses.field(default_factory=dict)
    _digest_memo: dict[int, tuple[jax.Array, tuple]] = \
        dataclasses.field(default_factory=dict)
    # The owning executor's tracer (None = tracing off).  Backends with
    # internally interesting structure (the sharded backend's per-device
    # scatter/gather loop) emit child spans through it; spans opened inside
    # an instrumented dispatch nest under the executor's stage span via
    # the tracer's lexical stack.  Typed loosely to keep backends importable
    # without the tracing module.
    tracer: "object | None" = None
    # The owning executor's timebase (``ManualClock`` in deterministic
    # tests/benches, ``time.perf_counter`` live).  Fault-aware backends
    # sleep injected straggles and stamp quarantine windows through it so
    # the whole fault story replays bit-identically under a manual clock.
    clock: "Callable[[], float]" = time.perf_counter
    # Devices declared lost for the *current* dispatch only (chaos
    # injection): the sharded backend's shard on a lost device raises
    # DeviceLostError and recovers on a survivor.  Cleared by the injector.
    lost_devices: frozenset = frozenset()
    # Fault-handling collaborators (duck-typed like ``tracer`` to keep
    # backends importable without the faults module): the executor's
    # Quarantine (sharded dispatch skips quarantined devices and records
    # new exclusions here) and its DispatchWatchdog (per-device straggler
    # deadlines).
    quarantine: "object | None" = None
    watchdog: "object | None" = None
    # The owning executor's RuntimeTelemetry: backends record their fault,
    # quarantine, placement, residency and delta events through it (a
    # context built alone gets a telemetry of its own).
    telemetry: RuntimeTelemetry = dataclasses.field(
        default_factory=RuntimeTelemetry)
    # The owning executor's operand residency cache
    # (``repro.runtime.residency.ResidencyCache``), or None for the
    # historical stage-every-flush behavior.  With a cache attached, the
    # shared ``stage_group`` helper serves staged stacks from it (and the
    # sharded backend keeps per-device placement sets), so repeat flushes
    # of unchanged operands skip staging and are priced read-side-only.
    residency: "object | None" = None
    # Which physical write stream ``stage_group`` is staging into: "host"
    # for the staged-stack path, ("device", d) when the sharded backend
    # runs the inner backend against one device's sub-group.  Delta
    # classification keys its per-slot code signatures by this, so two
    # devices' same-shaped sub-groups never diff against each other's
    # staged codes.
    stage_stream: "object" = "host"

    @property
    def block_budget(self) -> "MemoryBudget | None":
        """The budget one Pallas grid step is sized against: a TPU core's
        VMEM when the staging budget models HBM, otherwise the staging
        budget itself (the VMEM fallback, an off-TPU cache, an operator's
        pin, or none)."""
        if self.mem_budget is not None and self.mem_budget.source == "hbm":
            return MemoryBudget.vmem()
        return self.mem_budget

    def blocks_for(self, batch: int, h: int, w: int) -> "BlockPlan":
        """Resolved Pallas block sizes for a ``(batch, h, w)`` stacked DFT
        invocation, derived from ``block_budget`` (``choose_blocks``).

        Keyed by the stack shape AND the budget it was derived from:
        replanning ``tile_k`` changes the dispatched stack depth, and an
        operator swapping the budget changes the blocks — either way the
        resolution must be fresh, never a stale plan shaped for the old
        layout."""
        budget = self.block_budget
        key = (batch, h, w,
               None if budget is None else (budget.bytes_limit,
                                            budget.reserve))
        if key not in self.block_cache:
            self.block_cache[key] = choose_blocks(batch, h, w, w, budget)
        return self.block_cache[key]

    def factors(self, n: int,
                blocks: tuple = ()) -> tuple[jax.Array, jax.Array]:
        # Computed from host constants, so the cached matrices stay
        # *uncommitted*: jit moves them to whatever device a (possibly
        # sharded, committed) operand pins the computation to.  The key
        # carries the resolved block signature the matrices will be tiled
        # under: a replan that changes tile_k (hence the stack depth,
        # hence the budget-derived blocks) must never pair a freshly
        # compiled kernel with factors cached under the old layout — the
        # kernel jit-specializes on the block sizes, and keying the
        # factors identically keeps one cache entry per compiled layout.
        # The matrix *values* depend only on n, so layout entries alias
        # one shared pair (built once under the bare (n,) key) instead of
        # recomputing and holding duplicate O(n^2) arrays per layout.
        key = (n,) + tuple(blocks)
        if key not in self.factor_cache:
            base = self.factor_cache.setdefault((n,), dft_matrix_factors(n))
            self.factor_cache[key] = base
        return self.factor_cache[key]

    def content_key(self, kernel: jax.Array) -> tuple:
        """Content key of an operand: shape, dtype, SHA1 of the bytes.

        Content-keyed (not id-keyed): object identity can be recycled by
        the allocator after a temporary kernel dies, which would serve a
        stale cache entry.  Repeat hashing of a long-lived kernel is
        avoided by an id-keyed memo that HOLDS a reference to the array —
        a live entry pins the object, so a *recycled* id cannot alias
        while the memo is valid.  Pinning cannot protect against in-place
        mutation though: a writeable numpy buffer reused across submits
        is the same object with different bytes, so only immutable
        operands (jax arrays, read-only ndarrays) are memoized — mutable
        ones re-hash every time."""
        memo = self._digest_memo.get(id(kernel))
        if memo is not None and memo[0] is kernel:
            return memo[1]
        arr = np.asarray(kernel)
        key = (arr.shape, str(arr.dtype),
               hashlib.sha1(arr.tobytes()).hexdigest())
        if isinstance(kernel, np.ndarray) and kernel.flags.writeable:
            return key
        if len(self._digest_memo) >= 64:  # bounded: kernels are few
            self._digest_memo.clear()
        self._digest_memo[id(kernel)] = (kernel, key)
        return key

    def mask(self, kernel: jax.Array) -> jax.Array:
        # The key also carries the kernel's device placement: a kernel
        # committed to one device pins its mask there, and serving that
        # mask to a stack committed elsewhere would crash the jitted conv
        # with mixed-device operands.  (Uncommitted kernels — the usual
        # case, including sharded dispatch — yield an uncommitted mask
        # that follows whatever device the stack is committed to.)
        devs = getattr(kernel, "devices", None)
        dev_key = tuple(sorted(d.id for d in devs())) if callable(devs) \
            else ()
        key = self.content_key(kernel) + (dev_key,)
        if key not in self.mask_cache:
            self.mask_cache[key] = fourier_mask_for_kernel(kernel)
        return self.mask_cache[key]

    @property
    def sim_params(self) -> OpticalSimParams:
        return OpticalSimParams(dac_bits=self.spec.dac.bits,
                                adc_bits=self.spec.adc.bits)


class ExecutionBackend(abc.ABC):
    """One way of executing the planner's op categories."""

    name: str = "?"

    def supports(self, category: str, ctx: BackendContext) -> bool:
        if category not in CATEGORIES:
            return False
        if category == "matmul":
            return isinstance(ctx.spec, OpticalMVMAcceleratorSpec) \
                or self.name == "host"
        return isinstance(ctx.spec, OpticalFourierAcceleratorSpec) \
            or self.name == "host"

    @abc.abstractmethod
    def run(self, category: str, xs: Sequence[jax.Array], ctx: BackendContext,
            *, kernel: jax.Array | None = None,
            weights: jax.Array | None = None,
            ) -> tuple[list[jax.Array], StepCost | None]:
        """Execute a batch of same-shape requests.

        Returns per-item results and the modeled cost of the whole batch
        (None for backends whose cost is just their measured wall time)."""


def _samples(x: jax.Array) -> int:
    return int(x.size)


def stage_group(category: str, xs: Sequence[jax.Array], ctx: BackendContext,
                *, single_expand: bool = False,
                ) -> tuple[jax.Array, int, tuple]:
    """Stack a same-shape group into the dispatch operand, serving the
    staged stack from the context's residency cache on a content hit.

    Returns ``(stack, resident, delta_fractions)``: ``resident`` is how
    many of the group's items were already staged (``len(xs)`` on a
    group-grain hit), and ``delta_fractions`` the per-frame write scales
    of the items that changed *little enough* to take the delta-encoded
    partial write.  On a group miss each frame is classified against the
    operand last staged into its dispatch slot (the context's
    ``stage_stream`` + category + shape + position, via
    ``ResidencyCache.classify_operand``): an unchanged frame counts
    resident, a low-flip frame contributes its write scale, everything
    else re-stages in full.  The analog backends thread both into
    ``batched_step_cost(resident_frames=..., delta_fractions=...)`` so
    the modeled price matches what dispatch just skipped.  With no cache
    attached this is exactly the historical ``jnp.stack`` (or the host's
    single-item expand), bit for bit.

    Rerunning the same jitted computation on the same cached stack yields
    bit-identical results, which is how the runtime-equivalence invariant
    extends to cached == delta-staged == re-staged.
    """
    res = getattr(ctx, "residency", None)
    if res is None:
        if single_expand and len(xs) == 1:
            return xs[0][None], 0, ()
        return jnp.stack(list(xs)), 0, ()
    key = residency_key(ctx, xs, "frame")
    stack = res.lookup("host", key, category=category, ctx=ctx)
    if stack is not None:
        return stack, len(xs), ()
    if single_expand and len(xs) == 1:
        stack = xs[0][None]
    else:
        stack = jnp.stack(list(xs))
    res.store("host", key, stack,
              int(getattr(stack, "nbytes", stack.size * 4)),
              category=category, kind="frame", ctx=ctx)
    classify = getattr(res, "classify_operand", None)
    if classify is None:
        return stack, 0, ()
    # group-grain miss: classify each frame against its dispatch slot —
    # unchanged frames are still resident per-frame, drifted ones delta
    stream = getattr(ctx, "stage_stream", "host")
    shape_sig = (tuple(xs[0].shape), str(xs[0].dtype))
    op = key[1]
    resident = 0
    deltas: list[float] = []
    for i, ck in enumerate(key[2]):
        slot = (stream, category, "frame", op, shape_sig, i)
        label, scale = classify(slot, ck, xs[i], ctx.spec,
                                category=category, ctx=ctx)
        if label == "hit":
            resident += 1
        elif label == "delta":
            deltas.append(scale)
    return stack, resident, tuple(deltas)


def _operand_resident(category: str, arr: jax.Array, ctx: BackendContext,
                      kind: str) -> bool:
    """Whether a kernel/weight operand is resident (registering it when
    not): True means this invocation writes no weight samples."""
    res = getattr(ctx, "residency", None)
    if res is None or arr is None:
        return False
    key = residency_key(ctx, [arr], kind)
    if res.lookup("host", key, category=category, ctx=ctx) is not None:
        return True
    res.store("host", key, arr, int(getattr(arr, "nbytes", arr.size * 4)),
              category=category, kind=kind, ctx=ctx)
    return False


# --- host: the digital baseline ----------------------------------------------

# Each op accepts a leading batch axis natively: fft2/ifft2 act on the last
# two axes (the (H, W) kernel broadcasts under the (K, H, W) stack) and
# (K, m, k) @ (k, n) is a batched matmul.  One jitted call serves the group.


@jax.jit
def _host_fft_intensity(a: jax.Array) -> jax.Array:
    return jnp.abs(jnp.fft.fft2(a, norm="ortho")) ** 2


@jax.jit
def _host_circular_conv(a: jax.Array, k: jax.Array) -> jax.Array:
    return jnp.real(jnp.fft.ifft2(jnp.fft.fft2(a) * jnp.fft.fft2(k)))


@jax.jit
def _host_matmul(a: jax.Array, w: jax.Array) -> jax.Array:
    return a @ w


class HostBackend(ExecutionBackend):
    """Pure JAX execution; cost is whatever wall time the executor measures."""

    name = "host"

    def run(self, category, xs, ctx, *, kernel=None, weights=None):
        stack, _, _ = stage_group(category, xs, ctx, single_expand=True)
        if category == "fft":
            out = _host_fft_intensity(stack)
        elif category == "conv":
            out = _host_circular_conv(stack, kernel)
        elif category == "matmul":
            out = _host_matmul(stack, weights)
        else:
            raise ValueError(f"unknown category {category!r}")
        return list(out), None


# --- optical-sim: the conversion boundary, executed and priced ----------------


def conv_range_map(stack: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-frame affine map of arbitrary-range frames onto the SLM's [0, 1]
    aperture: the DAC's full-scale range is fixed and the SLM cannot encode
    negative amplitudes.  Conv is linear, so the map undoes exactly:
    conv(s*v + lo) = s*conv(v) + lo*sum(kernel) (circular conv of a
    constant plane is the kernel sum).  Shared by the batched conv path and
    the frame-sharded tiler — the two must use the SAME map (one grid of
    DAC quantization points) or sharded results drift from unsharded ones.
    """
    lo = jnp.min(stack, axis=(-2, -1), keepdims=True)
    scale = jnp.maximum(jnp.max(stack, axis=(-2, -1), keepdims=True) - lo,
                        1e-9)
    return lo, scale


@functools.partial(jax.jit, static_argnames=("params",))
def _optical_conv_batched(stack: jax.Array, mask: jax.Array, ksum: jax.Array,
                          params: OpticalSimParams) -> jax.Array:
    # lo/scale are per frame, and ``optical_conv2d_batched`` keeps the
    # interferometric ADC full-scale per frame too.
    lo, scale = conv_range_map(stack)
    v = (stack - lo) / scale
    out = optical_conv2d_batched(v, mask, params, None)
    return out * scale + lo * ksum


@functools.partial(jax.jit, static_argnames=("dac_bits", "adc_bits"))
def _optical_matmul_batched(stack: jax.Array, w: jax.Array, *,
                            dac_bits: int, adc_bits: int) -> jax.Array:
    # One streamed invocation: the batch stacks activation rows, but each
    # item keeps its own DAC range mapping and differential ADC ranges.
    def one(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-9)
        q = dac_quantize(0.5 * (a / scale + 1.0), dac_bits) * 2.0 - 1.0
        y = (q * scale) @ w
        pos = jnp.maximum(y, 0.0)
        neg = jnp.maximum(-y, 0.0)  # differential readout: two ADC ranges
        return adc_quantize(pos, adc_bits) - adc_quantize(neg, adc_bits)

    return jax.vmap(one)(stack)


class OpticalSimBackend(ExecutionBackend):
    """Simulated analog engine with DAC/ADC quantization applied.

    Every category executes the whole group in ONE batched invocation:
    ``fft`` runs the batched Pallas pipeline (``dft_stage1_batched``/
    ``dft_stage2_batched`` — batch on the leading grid axis, cached factor
    matrices shared across frames) then a per-frame auto-ranged ADC pass;
    ``conv`` runs the 4f physics simulator vmapped over the stacked batch;
    ``matmul`` streams the stacked activations through the converter
    models around one batched matmul standing in for the MVM core.  Every
    batch returns a :class:`StepCost` from the spec's
    ``batched_step_cost`` at the context's pipeline depth, so callers
    always see the (overlap-aware) boundary price.
    """

    name = "optical-sim"

    def _fft_batched(self, stack: jax.Array, ctx: BackendContext) -> jax.Array:
        if INTERPRET:
            # Off-TPU the Pallas interpreter copies the whole batched
            # output per grid step (a correctness simulator, not a perf
            # one): run the same fused semantics as one XLA dispatch.
            intensity = _dft2_intensity_batched_xla(
                stack, dac_bits=ctx.spec.dac.bits)
        else:
            batch, h, w = stack.shape
            # block sizes come from the VMEM budget, not fixed defaults;
            # factors are cached per resolved layout (see ctx.factors)
            plan = ctx.blocks_for(batch, h, w)
            whr, whi = ctx.factors(h, plan.key)
            wwr, wwi = ctx.factors(w, plan.key)
            tr, ti = dft_stage1_batched(whr, whi, stack,
                                        dac_bits=ctx.spec.dac.bits,
                                        bb=plan.bb, bm=plan.bm,
                                        bk=plan.bk, bn=plan.bn)
            intensity = dft_stage2_batched(tr, ti, wwr, wwi, bb=plan.bb,
                                           bm=plan.bm, bk=plan.bk,
                                           bn=plan.bn)
        return adc_quantize_batched(intensity, ctx.spec.adc.bits)

    def run(self, category, xs, ctx, *, kernel=None, weights=None):
        batch = len(xs)
        n_in = _samples(xs[0])
        stack, resident, deltas = stage_group(category, xs, ctx)
        depth = ctx.pipeline_depth
        priced_residency = getattr(ctx, "residency", None) is not None
        if category == "fft":
            out = self._fft_batched(stack, ctx)
            cost = ctx.spec.batched_step_cost(n_in, _samples(out[0]),
                                              batch=batch,
                                              pipeline_depth=depth,
                                              resident_frames=resident,
                                              delta_fractions=deltas)
        elif category == "conv":
            mask = ctx.mask(kernel)
            # registered before the mask build so a repeat kernel prices as
            # resident even though ctx.mask memoizes the mask either way
            k_resident = _operand_resident(category, kernel, ctx, "kernel")
            out = _optical_conv_batched(stack, mask, jnp.sum(kernel),
                                        ctx.sim_params)
            spec4 = dataclasses.replace(ctx.spec,
                                        phase_shift_captures=CONV_CAPTURES)
            k_n = _samples(kernel) if priced_residency else 0
            cost = spec4.batched_step_cost(
                n_in, _samples(out[0]), batch=batch, pipeline_depth=depth,
                resident_frames=resident, weight_samples=k_n,
                resident_weights=k_n if k_resident else 0,
                delta_fractions=deltas)
        elif category == "matmul":
            w_resident = _operand_resident(category, weights, ctx, "weights")
            out = _optical_matmul_batched(stack, weights,
                                          dac_bits=ctx.spec.dac.bits,
                                          adc_bits=ctx.spec.adc.bits)
            m, k = xs[0].shape
            n = weights.shape[-1]
            # Batching stacks activations along m: one streamed invocation.
            # With residency priced, a non-resident weight panel charges
            # its one-time DAC load (weight_write) and fully resident
            # activations drop the streaming DAC term: hits read-side-only.
            w_write = priced_residency and not w_resident
            cost = ctx.spec.matmul_cost(batch * m, k, n,
                                        weight_write=w_write)
            if resident >= batch:
                act_free = ctx.spec.dac.time_for(k * n, ctx.spec.dac_lanes) \
                    if w_write else 0.0
                cost = dataclasses.replace(cost, dac_s=act_free)
            elif deltas:
                # delta-staged activations: resident frames free, delta
                # frames at their write scale, the rest whole — same
                # resident → delta → full accounting as _group_sides
                written = batch - resident
                ws = (math.fsum(deltas) + (written - len(deltas))) / written
                col_tiles = math.ceil(n / ctx.spec.cols)
                w_dac = ctx.spec.dac.time_for(k * n, ctx.spec.dac_lanes) \
                    if w_write else 0.0
                act_dac = ctx.spec.dac.time_for(
                    written * m * k * col_tiles, ctx.spec.dac_lanes) * ws
                cost = dataclasses.replace(cost, dac_s=w_dac + act_dac)
            cost = dataclasses.replace(
                cost, interface_s=ctx.spec.interface_latency_s)
        else:
            raise ValueError(f"unknown category {category!r}")
        return list(out), cost


# --- ideal: the zero-conversion-cost analog bound -----------------------------


def ideal_step_cost(spec, category: str, calls: int) -> StepCost:
    """The zero-conversion analog bound for one invocation: physics only.

    Shared by :class:`IdealBackend` and the sharded tiler's per-device
    pricing so the Table-1 bound has exactly one definition."""
    if isinstance(spec, OpticalMVMAcceleratorSpec):
        analog = calls * spec.optical_pass_s
    else:
        caps = CONV_CAPTURES if category == "conv" \
            else spec.phase_shift_captures
        analog = ((spec.slm_settle_s + spec.exposure_s) * caps
                  + spec.time_of_flight_s())
    return StepCost(0.0, 0.0, 0.0, analog_s=analog)


class IdealBackend(ExecutionBackend):
    """Exact digital values, priced as if conversion and interface were free.

    This is the paper's Table-1 'ideal accelerator' column made executable:
    the only cost charged is the analog physics itself, so comparing a plan
    under ``ideal`` against ``optical-sim`` isolates exactly what the
    boundary costs.
    """

    name = "ideal"

    def run(self, category, xs, ctx, *, kernel=None, weights=None):
        outs, _ = _HOST.run(category, xs, ctx, kernel=kernel, weights=weights)
        return outs, ideal_step_cost(ctx.spec, category, len(xs))


_HOST = HostBackend()

_REGISTRY: dict[str, Callable[[], ExecutionBackend]] = {}


def register_backend(name: str, factory: Callable[[], ExecutionBackend]) -> None:
    """Register (or override) a backend under ``name``."""
    _REGISTRY[name] = factory


def get_backend(name: str) -> ExecutionBackend:
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; "
                       f"available: {available_backends()}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend("host", HostBackend)
register_backend("optical-sim", OpticalSimBackend)
register_backend("ideal", IdealBackend)
