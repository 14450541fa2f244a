"""Conversion-aware offload runtime: execute hybrid host/optical plans.

The seed repo *priced* the paper's conversion bottleneck (``repro.core``
returns an ``OffloadPlan`` nothing consumed); this package is the layer
that runs it.  Module map:

  backends   — registry of three interchangeable executors per op category:
               ``host`` (pure JAX fft/conv/matmul), ``optical-sim`` (fused
               Pallas DFT pipeline + 4f physics sim with the DAC/ADC
               boundary applied, every batch priced with a ``StepCost``),
               ``ideal`` (exact values at the zero-conversion analog bound).
  executor   — ``OffloadExecutor``: request queue that coalesces same-shape
               calls into ONE batched invocation (stacked operands, batched
               Pallas kernels / vmapped physics — amortizing per-call
               handshake latency, SLM settle/exposure, converter-lane ceil
               residue, AND the dispatch/launch overhead itself: the
               paper's §6 batching lever, executed rather than modeled),
               pipelined two deep (``flush_async``: invocation k+1 stages
               while invocation k computes; per-result ``wait``/``done``)
               behind per-``(category, backend)`` pipeline *windows*
               (``set_pipeline_window``): one engine's in-flight depth
               never gates another's, retirement stays submit-ordered
               within each engine, and the global ``pipeline_depth``
               remains the back-compat default for unpinned categories
               (``shared_window=True`` restores the old single gate),
               with per-category coalescing ceilings (``set_max_batch``),
               per-shape DFT-factor / Fourier-mask / jit caches, a public
               group-release primitive (``release``) the scheduler drives,
               and context-manager cleanup (``with`` drains queued, held,
               and in-flight work).
  scheduler  — ``OffloadScheduler``: admission-controlled continuous
               batching over the executor — partially filled groups are
               *held open across flushes* under a per-category deadline and
               released when full (``max_batch``), due (oldest age reaches
               the deadline), or futile to hold (the telemetry-estimated
               arrival rate says the next arrival lands past the deadline);
               hold time is priced into the invocation
               (``StepCost.hold_s``).  ``ManualClock`` makes admission
               deterministic in tests/benchmarks.
  telemetry  — ``RuntimeTelemetry``: measured per-category call counts,
               sample counts, wall time, and the submit arrival process
               (``arrival_rate``), emitted as ``CategoryProfile``s so
               ``plan_offload`` re-plans from observed traffic.
  fidelity   — ``FidelityChecker``: shadows optical-sim batches with the
               host reference (vectorized: one norm reduction + one sync
               per batch; ``sample_every`` bounds hot-path cost) and scores
               quantization error against the converters' ENOB budget,
               pairing speedups with accuracy — and *gating* planning:
               ``replan`` threads the worst observed error into each
               profile so an over-budget category is vetoed off the
               accelerator regardless of speedup.
  sharded    — ``ShardedOpticalBackend``: scatters one batched invocation
               across ``n_devices`` replicated simulated accelerators —
               group sharding (the stacked flush group splits across
               devices, each paying its own DAC/ADC crossing; modeled wall
               = max-over-devices + sync) or frame sharding (one large
               frame tiles onto multiple apertures with overlap-save halos
               for conv) — with mesh-aware device placement and an
               off-mesh sequential fallback (CPU tests).  With residency
               on, the backend commits one device-resident *placement*
               per ``(category, group shape)``: shards are
               ``device_put`` once and stay resident across tiles and
               flushes, only changed frames re-cross the DAC, gather
               happens only at ADC readout, and quarantine/device loss
               drops the placement and rebuilds it on the survivors.
  tiling     — ``MemoryBudget`` / ``choose_tile`` / ``choose_blocks``:
               memory-budgeted tiled dispatch.  A released flush group
               whose monolithic ``(K, H, W)`` stack would overflow the
               per-device staging budget (a share of HBM on TPU,
               LLC-derived off it) streams as ``ceil(K / tile_k)``
               sub-invocations through the same two-deep pipeline
               (write/analog/read overlap between tiles), and the batched
               Pallas DFT grid's block sizes are derived from a VMEM
               budget.  ``tile_k=1`` degenerates to looped, ``>= K`` to
               monolithic — the runtime-equivalence invariant covers all
               three.
  residency  — ``ResidencyCache``: per-device operand residency under the
               tiling ``MemoryBudget`` — content-keyed (operand digest +
               converter operating point) entries for flush-group frame
               stacks, conv kernels, matmul weight panels, and sharded
               per-device shard placements, LRU-evicted against the same
               staging budget tiles spend from.  A resident operand skips
               the write-side DAC crossing and host staging entirely
               (priced read-side-only by ``batched_step_cost``); hit /
               miss / eviction / invalidation counters land in
               ``RuntimeTelemetry`` and ``cache`` instants in the tracer.
               Opt-in: ``OffloadExecutor(residency=True)``.
  router     — ``PlanRouter``: applies an ``OffloadPlan``'s decisions as a
               category->backend routing table and closes the
               profile -> plan -> execute -> re-profile loop via ``replan``
               — adaptively: each category's ``max_batch``, sharded
               ``n_devices`` AND memory-budgeted ``tile_k`` are picked
               from observed telemetry (occupancy, per-call boundary
               traffic) under an optional latency ``deadline_s``, and
               each category's pipeline window collapses to its observed
               in-flight occupancy (``choose_windows``).
  faults     — the fault story for the conversion boundary:
               ``ChaosBackend`` wraps any registered backend with a
               deterministic seeded ``FaultSchedule`` (transient dispatch
               errors, latency-spike stragglers, ENOB drift, hard device
               loss); ``RetryPolicy`` gives every executor dispatch
               deadline/retry/backoff semantics with graceful degradation
               to the host backend; ``DispatchWatchdog`` applies the
               training runner's trailing-median straggler deadline to
               dispatch walls; ``Quarantine`` time-windows failing devices
               and categories out of the scatter/routing set with
               probation-based re-admission.  The equivalence invariant
               survives every fault: all frames retire, in order, with
               host-equal results.
  tracing    — ``Tracer`` / ``Span``: opt-in boundary-attributed span
               trees (``OffloadExecutor(tracer=...)``) — one tree per
               batched invocation covering submit -> held(reason) ->
               release(full|due|futile) -> tile -> stage -> compute ->
               fidelity-shadow, with per-device scatter children under
               sharded dispatch.  Zero overhead when off; injectable
               clock (``ManualClock``) for exact test assertions.
  metrics    — ``Counter`` / ``Histogram`` / ``MetricsRegistry``
               (mergeable log-binned percentile histograms).
  trace_export — Chrome/Perfetto ``trace_event`` JSON export
               (``write_trace``), per-stage charged sums
               (``stage_sums`` / ``reconcile``), one-screen digests
               (``summarize``).
  specs      — shared demo design points (``BATCHED_4F``: upgraded
               peripherals + frame latency that only batching amortizes).

Quick start::

    from repro.runtime import OffloadExecutor, PlanRouter
    ex = OffloadExecutor(PROTOTYPE_4F, max_batch=16)
    router = PlanRouter(ex)                   # all-host profiling mode
    ex.telemetry.start()
    outs = [router.run("fft", img) for img in imgs]
    ex.telemetry.stop()
    plan = router.replan()                    # measured plan; routes updated
"""

from repro.runtime.backends import (
    CATEGORIES,
    CONV_CAPTURES,
    BackendContext,
    ExecutionBackend,
    HostBackend,
    IdealBackend,
    OpticalSimBackend,
    available_backends,
    get_backend,
    register_backend,
)
from repro.runtime.executor import OffloadExecutor, OffloadResult
from repro.runtime.faults import (
    ChaosBackend,
    DeviceLostError,
    DispatchWatchdog,
    Fault,
    FaultError,
    FaultSchedule,
    Quarantine,
    QuarantineEvent,
    RetryPolicy,
    TransientDispatchError,
    advance_or_sleep,
    register_chaos,
)
from repro.runtime.fidelity import FidelityChecker, FidelityReport, enob_error_bound
from repro.runtime.metrics import (
    Counter,
    Histogram,
    MetricsRegistry,
)
from repro.runtime.residency import (
    DELTA_THRESHOLD,
    ResidencyCache,
    ResidencyEntry,
    operating_point,
    residency_key,
)
from repro.runtime.router import PlanRouter
from repro.runtime.scheduler import ManualClock, OffloadScheduler
from repro.runtime.sharded import ShardedOpticalBackend, kernel_halo, shard_sizes
from repro.runtime.specs import BATCHED_4F, CAMERA_ADC, SLM_DAC
from repro.runtime.telemetry import (
    BackendStats,
    DeltaStats,
    DeviceStats,
    RuntimeTelemetry,
    WindowStats,
)
from repro.runtime.tiling import (
    BlockPlan,
    MemoryBudget,
    TilePlan,
    choose_blocks,
    choose_tile,
    tile_sizes,
)
from repro.runtime.trace_export import (
    reconcile,
    stage_sums,
    summarize,
    to_trace_events,
    write_trace,
)
from repro.runtime.tracing import Span, Tracer

__all__ = [
    "CATEGORIES",
    "CONV_CAPTURES",
    "BackendContext",
    "ExecutionBackend",
    "HostBackend",
    "IdealBackend",
    "OpticalSimBackend",
    "available_backends",
    "get_backend",
    "register_backend",
    "OffloadExecutor",
    "OffloadResult",
    "ChaosBackend",
    "DeviceLostError",
    "DispatchWatchdog",
    "Fault",
    "FaultError",
    "FaultSchedule",
    "Quarantine",
    "QuarantineEvent",
    "RetryPolicy",
    "TransientDispatchError",
    "advance_or_sleep",
    "register_chaos",
    "FidelityChecker",
    "FidelityReport",
    "enob_error_bound",
    "ResidencyCache",
    "ResidencyEntry",
    "operating_point",
    "residency_key",
    "PlanRouter",
    "ManualClock",
    "OffloadScheduler",
    "ShardedOpticalBackend",
    "kernel_halo",
    "shard_sizes",
    "BackendStats",
    "DELTA_THRESHOLD",
    "DeltaStats",
    "DeviceStats",
    "RuntimeTelemetry",
    "WindowStats",
    "BlockPlan",
    "MemoryBudget",
    "TilePlan",
    "choose_blocks",
    "choose_tile",
    "tile_sizes",
    "BATCHED_4F",
    "CAMERA_ADC",
    "SLM_DAC",
    "Counter",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "reconcile",
    "stage_sums",
    "summarize",
    "to_trace_events",
    "write_trace",
]
